"""Per-architecture smoke tests: a REDUCED config of the same family runs
one forward/train step on CPU, asserting output shapes and no NaNs; plus
prefill->decode consistency against the full forward pass."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.build import build_model, make_batch

ARCHS = configs.names()


@pytest.mark.parametrize("name", ARCHS)
def test_train_step_smoke(name):
    cfg = configs.get(name).scaled()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, "train", 2, 32, seed=1)

    def step(p):
        loss, metrics = m.loss(p, batch, remat=True)
        return loss

    loss, grads = jax.value_and_grad(step)(params)
    assert jnp.isfinite(loss), f"{name}: loss not finite"
    assert float(loss) > 0.5
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.isfinite(leaf).all()), f"{name}: non-finite grad"


@pytest.mark.parametrize("name", ARCHS)
def test_forward_shapes(name):
    cfg = configs.get(name).scaled()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b, s = 2, 16
    batch = make_batch(cfg, "prefill", b, s, seed=2)
    cache = m.init_cache(b, 32)
    logits, cache2 = m.prefill(params, batch, cache)
    assert logits.shape == (b, 1, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_then_decode_matches_full_forward(name):
    """Prefill s tokens then decode one more == forward over s+1 tokens.

    MoE capacity-bounded routing legitimately breaks this identity when
    tokens overflow: the per-expert capacity depends on the total token
    count, so prefill(s)+decode(1) and prefill(s+1) drop *different*
    tokens.  The comparison is only well-defined in the no-drop regime,
    so MoE configs run with capacity_factor = n_experts (capacity >= all
    assignments; routing itself is still exercised)."""
    import dataclasses

    cfg = configs.get(name).scaled()
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         capacity_factor=float(cfg.moe.n_experts)))
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b, s = 2, 12
    full = make_batch(cfg, "prefill", b, s + 1, seed=3)

    # full forward: loss path exposes logits indirectly; use prefill on s+1
    cache_a = m.init_cache(b, 32)
    logits_full, _ = m.prefill(params, full, cache_a)

    # prefill s, then decode token s
    part = {k: (v[:, :s] if k in ("tokens", "labels") else v) for k, v in full.items()}
    cache_b = m.init_cache(b, 32)
    _, cache_b = m.prefill(params, part, cache_b)
    logits_dec, _ = m.decode_step(params, cache_b, full["tokens"][:, s : s + 1])

    np.testing.assert_allclose(
        np.asarray(logits_dec[:, -1], np.float32),
        np.asarray(logits_full[:, -1], np.float32),
        rtol=2e-3, atol=2e-3,
    )


def test_configs_match_assignment():
    """Exact hyperparameters from the assignment table."""
    rows = {
        "xlstm-125m": (12, 768, 4, 4, 0, 50304),
        "nemotron-4-15b": (32, 6144, 48, 8, 24576, 256000),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
    }
    for name, (L, d, h, kv, ff, v) in rows.items():
        cfg = configs.get(name)
        assert cfg.n_layers == L and cfg.d_model == d, name
        assert cfg.n_heads == h and cfg.n_kv_heads == kv, name
        assert cfg.d_ff == ff and cfg.vocab == v, name
    assert configs.get("qwen3-moe-30b-a3b").moe.n_experts == 128
    assert configs.get("qwen3-moe-30b-a3b").moe.top_k == 8
    assert configs.get("dbrx-132b").moe.n_experts == 16
    assert configs.get("dbrx-132b").moe.top_k == 4
    assert configs.get("zamba2-2.7b").ssm.d_state == 64
    # padded vocabs divisible by the 16-way model axis
    for name in rows:
        assert configs.get(name).padded_vocab % 16 == 0, name


def test_moe_dispatch_capacity_and_combine():
    from repro.nn.moe import moe_apply, moe_init

    cfg = configs.get("qwen3-moe-30b-a3b").scaled()
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
    out, aux = moe_apply(p, x, cfg)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(aux))
    assert float(aux) > 0.5  # balance loss near 1 for random routing


def test_long_context_skip_rules():
    from repro.configs.base import applicable_shapes

    for name in ARCHS:
        cfg = configs.get(name)
        shapes = [s.name for s in applicable_shapes(cfg)]
        if name in ("xlstm-125m", "zamba2-2.7b"):
            assert "long_500k" in shapes, name
        else:
            assert "long_500k" not in shapes, name


@pytest.mark.parametrize("name", configs.shares())
def test_expert_share_serves_but_refuses_training(name):
    """A share (one chip's experts of an expert-parallel deployment) keeps
    its router over every expert and holds only its own experts' weights;
    the training layer refuses it with a clear error instead of indexing
    experts that are not there."""
    cfg = configs.get(name).scaled()
    assert cfg.moe.is_share and cfg.moe.held < cfg.moe.n_experts
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    moe = params["blocks"]["moe"]
    assert moe["router"].shape[-1] == cfg.moe.n_experts
    assert moe["w_gate"].shape[1] == cfg.moe.held
    batch = make_batch(cfg, "train", 2, 16, seed=1)
    with pytest.raises(ValueError, match=f"holds {cfg.moe.held} of the {cfg.moe.n_experts}"):
        m.loss(params, batch)
