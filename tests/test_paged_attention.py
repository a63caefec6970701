"""Decode attention over the KV page pool, read in place.

The paged score and value programs (``stripe_decode.build_paged_*``) take
the keys and values as ``Paged(pool, layer, table, lengths)``: the Pallas
kernel (interpret mode here) DMAs only each slot's live pages through the
page table.  Checked against the gathered-window einsum at the served
head shapes (KV heads / group 8/4, 2/16, 4/8) in f32 and bf16, on slots of
length 1, one page exactly, a window less one row, an empty slot on its
garbage page, and recycled pages that hold stale or NaN rows past each
slot's length; the pages the kernel fetches are recorded DMA by DMA.
Greedy decode through the paged programs stays token for token with the
dense reference, and the engine's ``serve.kv.*`` counters count the live
pages of every step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro import configs
from repro.core import cache as stripe_cache
from repro.core.hwconfig import get_config
from repro.core.lower_jnp import Paged
from repro.models.build import build_model
from repro.obs import metrics as obs_metrics
from repro.serving import (EngineConfig, Request, SamplingParams, ServingEngine,
                           WaveEngine)
from repro.serving import stripe_decode as sd

PAGE, PPS, HD, LAYERS = 8, 4, 16, 2
WINDOW = PAGE * PPS
# one page exactly, a window less one row, an empty slot, one row, a mix
LENGTHS = (PAGE, WINDOW - 1, 0, 1, 2 * PAGE + 3)
HEADS = [(8, 4), (2, 16), (4, 8)]  # (KV heads, group): the served shapes


def _cfg(kv, g, dtype):
    return dataclasses.replace(configs.get("qwen3-4b"), n_heads=kv * g,
                               n_kv_heads=kv, head_dim=HD, dtype=dtype)


def _jc(backend):
    return sd.EngineLikeConfig(
        hw=get_config("tpu_v5e"), backend=backend, interpret=True, use_disk=False,
        cache=stripe_cache.CompilationCache(capacity=16, use_disk=False))


def _pool(kv, dtype, seed=0):
    """A two-layer pool as the engine keeps it: each slot's pages drawn at
    random from the shared pages, a slot's garbage page after them (an
    empty slot's whole row points there), and every row at or past a
    slot's length NaN in the layer read, as a recycled page may hold."""
    rng = np.random.RandomState(seed)
    m = len(LENGTHS)
    shared = m * PPS
    garbage = shared + np.arange(m)
    table = np.repeat(garbage[:, None], PPS, axis=1).astype(np.int32)
    free = list(rng.permutation(shared))
    for s, n in enumerate(LENGTHS):
        for j in range(-(-n // PAGE)):
            table[s, j] = free.pop()
    pool = rng.randn(LAYERS, shared + m, PAGE, kv, HD).astype(np.float32)
    live = np.zeros((shared + m, PAGE), bool)
    for s, n in enumerate(LENGTHS):
        for t in range(n):
            live[table[s, t // PAGE], t % PAGE] = True
    pool[1][~live] = np.nan
    return Paged(jnp.asarray(pool, dtype), jnp.int32(1), table=jnp.asarray(table),
                 lengths=jnp.asarray(LENGTHS, jnp.int32))


def _history():
    return (jnp.arange(WINDOW)[None, :]
            < jnp.asarray(LENGTHS)[:, None])[:, None, None, :]


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kv,g", HEADS)
def test_paged_programs_match_gathered_window(kv, g, dtype, backend):
    """Scores below each slot's length and the values equal the einsum
    over the gathered window (the pool's rows promoted to f32, the rows
    past a slot's length zero); the NaN rows past it add nothing."""
    cfg, jc = _cfg(kv, g, dtype), _jc(backend)
    scores = sd.build_paged_scores_program(cfg, len(LENGTHS), WINDOW, PAGE, jc)
    values = sd.build_paged_values_program(cfg, len(LENGTHS), WINDOW, PAGE, jc)
    for prog in (scores, values):
        assert prog.record.backend == backend
        assert set(prog.record.block_backends.values()) <= {"pallas"}, \
            prog.record.block_fallbacks
    pool = _pool(kv, dtype)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(len(LENGTHS), kv, g, HD), jnp.float32)
    p = jnp.where(_history(), jnp.asarray(rng.rand(len(LENGTHS), kv, g, WINDOW),
                                          jnp.float32), 0.0)
    window = pool.select().astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    want_s = jnp.einsum("bkgd,btkd->bkgt", q, window, precision=hi)
    want_o = jnp.einsum("bkgt,btkd->bkgd", p, window, precision=hi)
    got_s = scores({"Q": q, "K": pool})["S"]
    got_o = values({"P": p, "V": pool})["O"]
    np.testing.assert_allclose(np.where(_history(), got_s, 0.0),
                               np.where(_history(), want_s, 0.0), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-5)
    assert np.all(got_o[LENGTHS.index(0)] == 0.0)  # the empty slot sums nothing


@pytest.mark.parametrize("kv,g", HEADS)
def test_paged_kernel_fetches_only_live_pages(kv, g, monkeypatch):
    """Every DMA the kernels start is recorded (layer, page): each program
    call fetches each slot's live pages, once each, from the layer handed
    in, and no page past a slot's length, no garbage page."""
    fetched = []
    real = pltpu.make_async_copy

    def spy(src, dst, sem):
        copy = real(src, dst, sem)
        layer, page = src.transforms[-1].indices[:2]

        class Recorded:
            def start(self):
                jax.debug.callback(lambda a, b: fetched.append((int(a), int(b))),
                                   layer, page)
                copy.start()

            def wait(self):
                copy.wait()

        return Recorded()

    monkeypatch.setattr(pltpu, "make_async_copy", spy)
    cfg, jc = _cfg(kv, g, "bfloat16"), _jc("pallas")
    pool = _pool(kv, "bfloat16")
    table = np.asarray(pool.table)
    want = sorted((1, int(table[s, j])) for s, n in enumerate(LENGTHS)
                  for j in range(-(-n // PAGE)))
    q = jnp.ones((len(LENGTHS), kv, g, HD), jnp.float32)
    p = jnp.where(_history(), 1.0 / WINDOW, 0.0) * jnp.ones((1, kv, g, 1))
    for prog, inputs in (
            (sd.build_paged_scores_program(cfg, len(LENGTHS), WINDOW, PAGE, jc),
             {"Q": q, "K": pool}),
            (sd.build_paged_values_program(cfg, len(LENGTHS), WINDOW, PAGE, jc),
             {"P": p, "V": pool})):
        fetched.clear()
        jax.block_until_ready(prog(inputs))
        jax.effects_barrier()
        assert sorted(fetched) == want


def test_pages_per_block_follow_the_shapes():
    """The cost model picks the kernel's block of pages from the shapes:
    a smaller KV row makes a page cheaper to fetch and compute on, so at
    least as many pages go in a block; each choice divides the window and
    fills whole 128-lane rows."""
    from repro.core import cost

    jc = _jc("jnp")
    picks = {}
    for name in ("qwen3-4b", "chatglm3-6b", "qwen3-moe-30b-a3b-ep8"):
        cfg = configs.get(name)
        prog = sd.build_paged_scores_program(cfg, 16, 1024, 16, jc)
        blk = next(b for b in prog.program.entry.stmts if b.name.startswith("paged"))
        tag = next(t for t in blk.tags if t.startswith("paged_pages:"))
        picks[cfg.n_kv_heads] = int(tag.split(":")[1])
        assert picks[cfg.n_kv_heads] == cost.paged_block_pages(
            prog.program.source.entry.stmts[0], prog.program.buffers,
            get_config("tpu_v5e"), dict(get_config("tpu_v5e").passes)["autotile"])
    assert picks[2] >= picks[4] >= picks[8]
    for c in picks.values():
        assert 64 % c == 0 and (c * 16) % 128 == 0


def _gqa_model():
    cfg = configs.get("llama3-8b").scaled(n_layers=2, d_model=32, n_heads=4,
                                          n_kv_heads=2, d_ff=64, vocab=64,
                                          head_dim=16, vocab_pad_multiple=16)
    return build_model(cfg)


def test_paged_decode_matches_dense_reference():
    """Greedy decode through the paged Pallas programs, prompts ending
    short of, on and past page boundaries, equals the dense-cache wave
    engine token for token; the step's KV counters count each slot's live
    pages, K and V of every layer."""
    model = _gqa_model()
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    plens = [1, 7, 8, 9, 16, 23]
    reqs = [Request(uid=i, prompt=rng.randint(1, model.cfg.vocab, size=n).astype(np.int32),
                    sampling=SamplingParams(max_new_tokens=6))
            for i, n in enumerate(plens)]
    want = {}
    for r in reqs:
        ref = WaveEngine(model, 1, 48)
        ref.submit(Request(uid=r.uid, prompt=r.prompt.copy(),
                           sampling=SamplingParams(max_new_tokens=6)))
        want[r.uid] = ref.run(params, max_steps=4096)[0].out_tokens
    ctr = {n: obs_metrics.counter(f"serve.kv.{n}") for n in ("pages_read", "pages_window")}
    before = {n: c.value for n, c in ctr.items()}
    eng = ServingEngine(model, EngineConfig(slots=1, max_len=48, page_size=8,
                                            backend="pallas"))
    paged = {k: r for k, r in eng.compile_records().items() if "paged" in k}
    assert set(paged) == {"decode/paged_scores", "decode/paged_values"}
    for rec in paged.values():
        assert set(rec.block_backends.values()) == {"pallas"}, rec.block_fallbacks
    for r in reqs:
        eng.submit(r)
    done = eng.run(params, max_steps=4096)
    assert {r.uid: r.out_tokens for r in done} == want
    # one slot: a request of prompt p decodes from positions p .. p + 4
    per_page = 2 * model.cfg.n_layers
    read = sum(per_page * -(-pos // 8) for p in plens for pos in range(p, p + 5))
    steps = 5 * len(plens)
    assert ctr["pages_read"].value - before["pages_read"] == read
    assert ctr["pages_window"].value - before["pages_window"] == per_page * steps * 6
