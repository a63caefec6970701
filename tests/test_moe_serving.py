"""Routed experts on the served path, at test widths on the CPU.

The engine's paged prefill and decode against the plain reference
(``benchmarks/chip/configs/moe_decoder.py``, float32) on seeded random
weights, with every expert held and with a share held; the share law (the
parts every share gives add up to the uncut layer); a router that sends
every token to the same held experts drops nothing; the grouped program's
per-block ``Stacked`` lowering in Pallas interpret mode against the jnp
lowering; and the engine's expert counters against the reference's own
routing."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, configs
from repro.configs.base import MoECfg
from repro.core import TileProgram, stripe_jit
from repro.core import cache as _cache
from repro.core.hwconfig import get_config
from repro.core.lower_jnp import Stacked
from repro.models.build import build_model
from repro.obs import metrics as obs_metrics
from repro.serving import paged
from repro.serving import stripe_decode as sd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HW = get_config("tpu_v5e")


def _reference():
    path = os.path.join(ROOT, "benchmarks", "chip", "configs", "moe_decoder.py")
    spec = importlib.util.spec_from_file_location("moe_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _cfg(n_held=0, held_offset=0, n_experts=8, top_k=4):
    cfg = configs.get("qwen3-moe-30b-a3b-ep8").scaled(n_layers=2)
    return dataclasses.replace(cfg, moe=MoECfg(
        n_experts=n_experts, top_k=top_k, d_ff_expert=32, n_held=n_held,
        held_offset=held_offset))


def _model_block(cfg):
    """The reference's view of ``cfg``: what a configuration file holds."""
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd, "d_ff": cfg.moe.d_ff_expert,
            "act": cfg.act, "vocab": cfg.vocab, "tie_embeddings": cfg.tie_embeddings,
            "qk_norm": cfg.qk_norm, "rotary_dims": cfg.hd, "rope_theta": cfg.rope_theta,
            "norm_eps": 1e-6, "dtype": cfg.dtype, "n_experts": cfg.moe.n_experts,
            "top_k": cfg.moe.top_k, "n_held": cfg.moe.held,
            "held_offset": cfg.moe.held_offset}


def _params(cfg, seed=3):
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return REF.make_weights(shapes, jax.random.PRNGKey(seed))


def _serve(cfg, params, prompts, max_new, backend, slots=4):
    eng = api.ServingEngine(build_model(cfg), api.EngineConfig(
        slots=slots, max_len=64, page_size=16, backend=backend, interpret=True,
        use_disk_cache=False))
    outs = {}
    for uid, tok in eng.generate(prompts, params=params, max_steps=1000,
                                 sampling=api.SamplingParams(max_new_tokens=max_new)):
        outs.setdefault(uid, []).append(int(tok))
    eng.close()
    return [outs[i] for i in range(len(prompts))]


PROMPTS = [np.random.default_rng(0).integers(0, 128, n).astype(np.int32)
           for n in (5, 11, 17, 9)]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("held", ["all", "share"])
def test_engine_matches_reference_on_logits(backend, held):
    """Prefill, then decode through the paged cache: at every served
    position the served token's logit is the reference's best (float32
    both, so to rounding), and the token is the reference's argmax."""
    cfg = _cfg() if held == "all" else _cfg(n_held=2, held_offset=2)
    params = _params(cfg)
    outs = _serve(cfg, params, PROMPTS, 10, backend)
    assert [len(o) for o in outs] == [10] * len(PROMPTS)
    REF.served_gaps(params, _model_block(cfg), PROMPTS, outs)
    gaps = REF.last_report["gaps"]
    assert float(np.max(gaps)) < 1e-4, gaps
    assert REF.last_report["positions"] == 10 * len(PROMPTS)


def _layer_inputs(cfg, m=12, seed=1):
    """A normed input (unit RMS, as the block's norm gives it) and a
    residual."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(m, cfg.d_model))
    x = jnp.asarray(x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True)), jnp.float32)
    resid = jnp.asarray(rng.normal(size=(m, cfg.d_model)), jnp.float32)
    return x, resid


def _jc(backend):
    return sd.EngineLikeConfig(hw=HW, backend=backend, interpret=True, use_disk=False,
                               cache=_cache.CompilationCache(capacity=64, use_disk=False))


def _served_layer(cfg, p_moe, x, resid, layer=1, backend=None, valid=None):
    """The served expert block of one layer: (out, (pairs, experts hit))."""
    blocks = (paged.PlainBlocks(cfg.act) if backend is None else
              sd.build_programs(cfg, x.shape[0], _jc(backend)))
    experts = {k: p_moe[k] for k in ("w_gate", "w_up", "w_down")}
    valid = jnp.ones(x.shape[0], bool) if valid is None else valid
    out, st = jax.jit(lambda x, r, e, router: paged._moe_ffn(
        cfg, blocks, x, r, e, router, layer, valid))(x, resid, experts,
                                                   p_moe["router"][layer])
    return np.asarray(out), np.asarray(st)


def _reference_layer(cfg, p_moe, x, layer=1):
    """The reference's expert block, less its residual, on a normed input
    (its norm scale taken as 1, so it norms it again, to rounding)."""
    m = _model_block(cfg)
    e = {"ln2": jnp.ones(cfg.d_model), "router": p_moe["router"][layer],
         "w_gate": p_moe["w_gate"][layer], "w_up": p_moe["w_up"][layer],
         "w_down": p_moe["w_down"][layer]}
    with jax.default_matmul_precision("highest"):
        y, *_ = REF._experts(x[None], e, REF.dense._frozen(m), "f32")
    return np.asarray(y[0] - x)


def _split(p_moe, lo, n):
    return dict(p_moe, **{k: p_moe[k][:, lo: lo + n]
                          for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("path", ["reference", "jnp", "pallas"])
def test_shares_add_up_to_the_uncut_layer(path):
    """Four shares of two experts each: the expert parts they give add up
    to what the uncut layer (all eight held) gives, with the residual
    counted once."""
    whole = _cfg()
    p_moe = _params(whole)["blocks"]["moe"]
    x, resid = _layer_inputs(whole)
    if path == "reference":
        full = _reference_layer(whole, p_moe, x)
        parts = [_reference_layer(_cfg(n_held=2, held_offset=lo), _split(p_moe, lo, 2), x)
                 for lo in range(0, 8, 2)]
        np.testing.assert_allclose(sum(parts), full, rtol=1e-5, atol=1e-5)
        return
    backend = None if path == "jnp" else "pallas"
    full, _ = _served_layer(whole, p_moe, x, resid, backend=backend)
    parts = [_served_layer(_cfg(n_held=2, held_offset=lo), _split(p_moe, lo, 2),
                           x, resid, backend=backend)[0] - np.asarray(resid)
             for lo in range(0, 8, 2)]
    np.testing.assert_allclose(sum(parts) + np.asarray(resid), full,
                               rtol=1e-4, atol=1e-4)
    # and the served uncut layer is the reference's
    ref = _reference_layer(whole, p_moe, x)
    np.testing.assert_allclose(full - np.asarray(resid), ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", [None, "pallas"])
def test_adversarial_router_drops_nothing(backend):
    """A router that sends all 16 tokens to the same two held experts
    (each then meets 16 rows, two blocks of 8) loses no row: the output
    is each token's two experts, weighted, and the counters say 32 pairs
    on 2 experts."""
    cfg = _cfg(n_held=2, held_offset=0, n_experts=8, top_k=4)
    p_moe = dict(_params(cfg)["blocks"]["moe"])
    router = np.zeros(p_moe["router"].shape, np.float32)
    router[:, :, 0] = 50.0   # experts 0 and 1 (held) first, 2 and 3 next
    router[:, :, 1] = 40.0
    router[:, :, 2] = 30.0
    router[:, :, 3] = 20.0
    p_moe["router"] = jnp.asarray(router)
    x, _ = _layer_inputs(cfg, m=16, seed=4)
    x = jnp.abs(x)  # all features positive: the router's columns decide alone
    resid = jnp.zeros_like(x)
    out, st = _served_layer(cfg, p_moe, x, resid, backend=backend)
    assert st.tolist() == [32, 2]
    ref = _reference_layer(cfg, p_moe, x)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    assert np.abs(out).min(axis=1).max() > 0


def _grouped(backend, nb, bm, d, f):
    tp = TileProgram("grouped_m4")
    tp.input("X", (nb, bm, d))
    tp.input("Wg", (nb, d, f), "bfloat16", indexed=True)
    tp.input("Wu", (nb, d, f), "bfloat16", indexed=True)
    tp.input("Wd", (nb, f, d), "bfloat16", indexed=True)
    tp.temp("G", (nb, bm, f))
    tp.temp("U", (nb, bm, f))
    tp.temp("A", (nb, bm, f))
    tp.output("Y", (nb, bm, d))
    tp.op("G[n, r, f] += X[n, r, d] * Wg[n, d, f]", name="mm_gate")
    tp.op("U[n, r, f] += X[n, r, d] * Wu[n, d, f]", name="mm_up")
    tp.op("A[n, r, f] = silu(G[n, r, f]) * U[n, r, f]", name="glu")
    tp.op("Y[n, r, d2] += A[n, r, f] * Wd[n, f, d2]", name="mm_down")
    return stripe_jit(tp.build(), HW, backend=backend, interpret=True, use_disk=False,
                      cache=_cache.CompilationCache(capacity=8, use_disk=False))


@pytest.mark.parametrize("live", [
    [1, 1, 1, 1, 1, 1],   # every block
    [1, 0, 1, 1, 0, 0],   # dead blocks between and after
    [0, 0, 1, 0, 1, 1],   # dead blocks before the first live one
    [0, 0, 0, 0, 0, 0],   # none (nothing to compare; it must still run)
])
def test_grouped_per_block_lowering(live):
    """The grouped program with one expert per block, ``Stacked`` with an
    index vector: Pallas (interpret) equals the jnp lowering on every live
    block; every index reads its expert where it lies."""
    nb, bm, d, f, experts = 6, 8, 256, 128, 5
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(nb, bm, d)), jnp.float32)
    w = {k: jnp.asarray(rng.normal(size=(experts,) + s) / 16, jnp.bfloat16)
         for k, s in (("Wg", (d, f)), ("Wu", (d, f)), ("Wd", (f, d)))}
    index = jnp.asarray([3, 3, 1, 4, 0, 0], jnp.int32)
    flags = jnp.asarray(live, jnp.int32)
    outs = {}
    for backend in ("jnp", "pallas"):
        prog = _grouped(backend, nb, bm, d, f)
        assert prog.record.backend == backend
        outs[backend] = np.asarray(jax.jit(lambda x, w: prog(
            {"X": x, **{k: Stacked(v, index, flags) for k, v in w.items()}})["Y"])(x, w))
    keep = np.asarray(live, bool)
    np.testing.assert_allclose(outs["pallas"][keep], outs["jnp"][keep],
                               rtol=1e-5, atol=1e-5)
    # the jnp lowering is the plain gathered computation
    wg, wu, wd = (np.asarray(w[k], np.float32)[np.asarray(index)] for k in ("Wg", "Wu", "Wd"))
    xs = np.asarray(x)
    g = np.einsum("nrd,ndf->nrf", xs, wg)
    want = np.einsum("nrf,nfd->nrd", g / (1 + np.exp(-g)) * np.einsum("nrd,ndf->nrf", xs, wu), wd)
    np.testing.assert_allclose(outs["jnp"], want, rtol=2e-4, atol=2e-4)


def test_live_flags_need_the_block_axis_outermost():
    """Dead blocks repeat a live neighbour's indices only where the
    per-block axis is the outermost that varies; anywhere else the launch
    raises instead of computing (and fetching) the dead blocks."""
    from repro.core import lower_pallas

    launch = lower_pallas._kernel_launcher(
        lambda x_ref, o_ref: None, (2, 3), [((1, 8, 128), lambda i, j: (j, 0, 0), 1)],
        (8, 128), lambda i, j: (i, j), jax.ShapeDtypeStruct((16, 384), jnp.float32),
        [], True, "t", {})
    w = jnp.zeros((5, 8, 128), jnp.float32)
    flags = jnp.asarray([1, 0, 1], jnp.int32)
    with pytest.raises(ValueError, match="outermost"):
        launch([(w, Stacked(w, jnp.asarray([4, 0, 2], jnp.int32), flags))])


def test_indexed_input_is_tiled_by_one():
    """The autotiler gives the dim that an ``indexed`` input's leading
    dim is addressed by a tile of 1, and fusion prices it so."""
    prog = _grouped("pallas", 32, 8, 2048, 768)
    tiles = list(prog.record.tilings.values())
    assert tiles and all(t["n"] == 1 for t in tiles if "d" in t or "d2" in t)
    assert set(prog.record.block_backends) == {"mm_gate", "mm_up+glu", "mm_down"}


def _routing_counts(cfg, params, prompts, outs):
    """From the reference's own routing: (decode pairs, decode experts
    hit, prefill pairs, prefill experts hit), every request decoding in
    lockstep (one slot each, equal output lengths)."""
    picks = []
    seqs = [list(p) + list(o[:-1]) for p, o in zip(prompts, outs)]
    REF.hidden_at(params, _model_block(cfg), seqs, [[0]] * len(seqs), picks=picks)
    lo, hi = cfg.moe.held_offset, cfg.moe.held_offset + cfg.moe.held
    held = [(p >= lo) & (p < hi) for p in picks]          # (layers, len, k)
    pre_rows = sum(int(h[:, : len(p)].sum()) for h, p in zip(held, prompts))
    pre_hit = sum(len({int(e) for e in pk[i, : len(p)].ravel() if lo <= e < hi})
                  for pk, p in zip(picks, prompts) for i in range(cfg.n_layers))
    steps = len(outs[0]) - 1
    dec_rows = dec_hit = 0
    for s in range(steps):
        at = [len(p) + s for p in prompts]
        for i in range(cfg.n_layers):
            es = [int(e) for pk, t in zip(picks, at) for e in pk[i, t] if lo <= e < hi]
            dec_rows += len(es)
            dec_hit += len(set(es))
    return dec_rows, dec_hit, pre_rows, pre_hit


def test_counters_equal_the_reference_routing():
    cfg = _cfg(n_held=2, held_offset=4)
    params = _params(cfg, seed=5)
    before = obs_metrics.snapshot()["counters"]
    outs = _serve(cfg, params, PROMPTS, 8, "jnp")
    after = obs_metrics.snapshot()["counters"]
    got = {k: after.get(f"serve.moe.{k}", 0) - before.get(f"serve.moe.{k}", 0)
           for k in ("decode_steps", "decode_rows", "decode_experts_hit",
                     "prefill_calls", "prefill_rows", "prefill_experts_hit")}
    dec_rows, dec_hit, pre_rows, pre_hit = _routing_counts(cfg, params, PROMPTS, outs)
    assert got == {"decode_steps": 7, "decode_rows": dec_rows,
                   "decode_experts_hit": dec_hit, "prefill_calls": len(PROMPTS),
                   "prefill_rows": pre_rows, "prefill_experts_hit": pre_hit}
    assert dec_rows > 0 and pre_rows > 0


def test_moe_rows_hold_every_pair():
    """The row buffer holds the worst case: every token's picks all held,
    each expert's rows padded to whole blocks."""
    full = configs.get("qwen3-moe-30b-a3b-ep8")
    assert sd.moe_rows(full, 16) == (8, 32)
    assert sd.moe_rows(full, 256) == (16, 144)
    for cfg in (_cfg(n_held=2), _cfg()):
        for m in (1, 4, 16, 64):
            bm, nb = sd.moe_rows(cfg, m)
            worst = m * min(cfg.moe.top_k, cfg.moe.held)
            assert nb * bm >= worst + cfg.moe.held * (bm - 1)
