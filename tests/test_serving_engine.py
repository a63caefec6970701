"""Continuous-batching serving engine: paged-KV correctness vs the dense
reference, slot admission/eviction invariants, compile-cache traffic, disk
warm-start, streaming, and the legacy-API shim."""
import numpy as np
import pytest

from repro import configs
from repro.core import cache as stripe_cache
from repro.models.build import build_model
from repro.serving import (EngineConfig, Request, SamplingParams,
                           ServingEngine, WaveEngine)


def _tiny_cfg():
    return configs.get("llama3-8b").scaled(n_layers=2, d_model=32, n_heads=2,
                                           n_kv_heads=2, d_ff=64, vocab=64,
                                           head_dim=16, vocab_pad_multiple=16)


@pytest.fixture(scope="module")
def model():
    return build_model(_tiny_cfg())


@pytest.fixture(scope="module")
def params(model):
    import jax
    return model.init(jax.random.PRNGKey(0))


def _mk_requests(cfg, plens, new=6, base_uid=0, seed=3):
    r = np.random.RandomState(seed)
    return [Request(uid=base_uid + i,
                    prompt=r.randint(1, cfg.vocab, size=p).astype(np.int32),
                    sampling=SamplingParams(max_new_tokens=new))
            for i, p in enumerate(plens)]


def _engine(model, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 8)
    return ServingEngine(model, EngineConfig(**kw))


def _dense_reference(model, params, reqs, max_len=48):
    """Greedy tokens from the dense-cache wave engine, one request at a
    time (batch-1, so no cross-request padding effects)."""
    out = {}
    for r in reqs:
        ref = WaveEngine(model, 1, max_len)
        ref.submit(Request(uid=r.uid, prompt=r.prompt.copy(),
                           sampling=SamplingParams(
                               max_new_tokens=r.sampling.max_new_tokens,
                               eos_id=r.sampling.eos_id)))
        done = ref.run(params, max_steps=4096)
        out[r.uid] = done[0].out_tokens
    return out


# ----------------------------------------------------------- correctness
def test_paged_matches_dense_reference_mixed_lengths(model, params):
    reqs = _mk_requests(model.cfg, [3, 8, 13, 21, 32, 5], new=7)
    want = _dense_reference(model, params, reqs)
    eng = _engine(model, slots=3)
    for r in reqs:
        eng.submit(r)
    done = eng.run(params, max_steps=4096)
    assert sorted(r.uid for r in done) == sorted(r.uid for r in reqs)
    for r in done:
        assert r.out_tokens == want[r.uid], \
            f"uid {r.uid}: paged decode diverged from dense reference"


def _tiny_moe_cfg():
    import dataclasses

    from repro.configs.base import MoECfg

    cfg = configs.get("qwen3-moe-30b-a3b-ep8").scaled(n_layers=2)
    return dataclasses.replace(cfg, moe=MoECfg(n_experts=8, top_k=4, d_ff_expert=32))


_FAMILIES = {"dense": _tiny_cfg, "moe": _tiny_moe_cfg}


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_paged_bf16_matches_cast_outside(family):
    """At a bf16 configuration the served Pallas programs read each
    layer's stored weights in place from the stacked parameter and promote
    them in the kernel.  Greedy tokens match the same programs lowered
    through the ``jnp`` backend, which slices each weight and casts it to
    f32 outside the dot (``lower_jnp.select_stacked``).  (The dense
    reference rounds its bf16 matmul outputs, so it is not token-exact at
    bf16.)"""
    import dataclasses

    import jax

    model = build_model(dataclasses.replace(_FAMILIES[family](), dtype="bfloat16"))
    params = model.init(jax.random.PRNGKey(0))
    plens = [3, 8, 13, 21, 32, 5]
    runs = {}
    for backend in ("jnp", "pallas"):
        eng = _engine(model, slots=3, backend=backend)
        for r in _mk_requests(model.cfg, plens, new=7):
            eng.submit(r)
        runs[backend] = {r.uid: r.out_tokens for r in eng.run(params, max_steps=4096)}
    assert sorted(runs["pallas"]) == list(range(len(plens)))
    assert runs["pallas"] == runs["jnp"]
    ffn = "decode/moe" if family == "moe" else "decode/mlp"
    for key in ("decode/qkv", "decode/attn_out", ffn):
        reads = eng.compile_records()[key].stored_reads
        assert reads and all(e["narrow"] and e["in_place"] for e in reads.values()), reads


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_plain_prefill_matches_programs(family):
    """The prefill a quarantined bucket falls back to (the layer over
    ``PlainBlocks``) gives the programs' first token and writes the same
    KV pages, dense and with routed experts (whose (pairs, experts hit)
    follow the token)."""
    import jax
    import jax.numpy as jnp

    from repro.core.hwconfig import get_config
    from repro.serving import stripe_decode as sd
    from repro.serving.paged import PlainBlocks, init_pages, make_prefill_step

    cfg = _FAMILIES[family]()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    ps, pps, bucket, length = 8, 6, 16, 11
    jc = sd.EngineLikeConfig(hw=get_config("tpu_v5e"), backend="jnp", use_disk=False,
                             cache=stripe_cache.CompilationCache(capacity=8, use_disk=False))
    tokens = jnp.asarray(np.random.RandomState(1).randint(1, cfg.vocab, (1, bucket)),
                         jnp.int32)
    row = jnp.asarray([5, 2, 0, 7, 7, 7], jnp.int32)  # page 7: the garbage page
    outs = {}
    for name, blocks in (("plain", PlainBlocks(cfg.act)),
                         ("programs", sd.build_programs(cfg, bucket, jc))):
        pk, pv = init_pages(cfg, 8, ps)
        step = jax.jit(make_prefill_step(cfg, blocks, ps, bucket))
        outs[name] = jax.tree_util.tree_map(
            np.asarray, step(params, tokens, jnp.int32(length), row, pk, pv))
    (tok_a, pk_a, pv_a), (tok_b, pk_b, pv_b) = outs["plain"], outs["programs"]
    assert tok_a.shape == ((3,) if family == "moe" else ())
    np.testing.assert_array_equal(tok_a, tok_b)
    assert np.any(pk_a[:, 5]) and not np.any(pk_a[:, 1])
    np.testing.assert_allclose(pk_a, pk_b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(pv_a, pv_b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_decode_step_owns_the_pools(family):
    """The engine donates both KV pools to its decode step: the compiled
    step aliases them to its outputs (the new rows are written in place,
    no copy of either pool), every pool a served step was handed is
    consumed, and greedy tokens equal those of the same step jitted
    without donation, which copies both pools each step."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.serving.paged import make_decode_step

    model = build_model(dataclasses.replace(_FAMILIES[family](), dtype="bfloat16"))
    params = model.init(jax.random.PRNGKey(0))
    plens = [3, 8, 13, 21, 32, 5]
    eng = _engine(model, slots=3)
    zeros = jnp.zeros(eng.slots, jnp.int32)
    table = jnp.asarray(eng._page_table)
    compiled = eng._decode_fn.lower(params, eng._pk, eng._pv, table, zeros, zeros).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= eng._pk.nbytes + eng._pv.nbytes

    handed = []
    step = eng._decode_fn

    def spy(params, pk, pv, *rest):
        handed.append((pk, pv))
        return step(params, pk, pv, *rest)

    eng._decode_fn = spy
    for r in _mk_requests(model.cfg, plens, new=7):
        eng.submit(r)
    got = {r.uid: r.out_tokens for r in eng.run(params, max_steps=4096)}
    assert len(handed) > 1  # the warm-up and the served steps
    assert all(pk.is_deleted() and pv.is_deleted() for pk, pv in handed)
    assert not (eng._pk.is_deleted() or eng._pv.is_deleted())

    copying = _engine(model, slots=3)
    copying._decode_fn = jax.jit(make_decode_step(model.cfg, copying.decode_programs(),
                                                  copying._ps))
    for r in _mk_requests(model.cfg, plens, new=7):
        copying.submit(r)
    want = {r.uid: r.out_tokens for r in copying.run(params, max_steps=4096)}
    assert sorted(got) == list(range(len(plens)))
    assert got == want


def test_determinism_across_runs(model, params):
    def run_once():
        eng = _engine(model)
        for r in _mk_requests(model.cfg, [4, 11, 7, 16, 9], new=5):
            eng.submit(r)
        return {r.uid: r.out_tokens for r in eng.run(params, max_steps=4096)}
    a, b = run_once(), run_once()
    assert a == b


# ----------------------------------------------- slot + page accounting
def test_freed_slot_reused_before_queue_growth(model, params):
    """Continuous batching's defining invariant: a finish that frees a
    slot while requests are queued is followed by an admit into that same
    slot at the very next admission phase (same or next step)."""
    eng = _engine(model, slots=2)
    reqs = _mk_requests(model.cfg, [8] * 6, new=5)
    for r in reqs:
        eng.submit(r)
    eng.run(params, max_steps=4096)
    ev = eng.events()
    admits = [e for e in ev if e["event"] == "admit"]
    assert len(admits) == len(reqs)
    for i, e in enumerate(ev):
        if e["event"] != "finish" or e["queue_depth"] == 0:
            continue
        later = [x for x in ev[i + 1:]
                 if x["event"] == "admit" and x["slot"] == e["slot"]]
        assert later, f"slot {e['slot']} freed with queue depth " \
                      f"{e['queue_depth']} but never refilled"
        assert later[0]["step"] <= e["step"] + 1, \
            "freed slot sat idle while the queue was non-empty"


def test_all_pages_released_after_run(model, params):
    eng = _engine(model, slots=2)
    for r in _mk_requests(model.cfg, [5, 17, 9, 30], new=6):
        eng.submit(r)
    eng.run(params, max_steps=4096)
    m = eng.metrics()
    assert m["finished"] == 4
    assert m["free_pages"] == eng.config.pool_pages
    # every slot's page-table row points back at its own garbage page
    for s in range(eng.slots):
        assert (eng._page_table[s] == eng._pool.garbage_page(s)).all()


def test_constrained_pool_blocks_then_proceeds(model, params):
    # pool of 6 pages, each request needs 3 -> at most 2 concurrent even
    # though 4 slots exist; everything still finishes.
    eng = _engine(model, slots=4, max_len=48, page_size=8, pages=6)
    reqs = _mk_requests(model.cfg, [16] * 5, new=8)
    want = _dense_reference(model, params, reqs)
    for r in reqs:
        eng.submit(r)
    done = eng.run(params, max_steps=4096)
    assert len(done) == 5
    concurrent, peak = 0, 0
    for e in eng.events():
        if e["event"] == "admit":
            concurrent += 1
            peak = max(peak, concurrent)
        elif e["event"] == "finish":
            concurrent -= 1
    assert peak <= 2, f"page pool should cap concurrency at 2, saw {peak}"
    for r in done:
        assert r.out_tokens == want[r.uid]


def test_oversized_request_rejected(model):
    eng = _engine(model, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(_mk_requests(model.cfg, [17])[0])


# ------------------------------------------------------ compile pipeline
def test_decode_runs_through_stripe_jit(model, params):
    eng = _engine(model)
    for r in _mk_requests(model.cfg, [6, 12], new=4):
        eng.submit(r)
    eng.run(params, max_steps=4096)
    recs = eng.compile_records()
    for block in ("qkv", "attn_out", "mlp"):
        assert f"decode/{block}" in recs
    mlp = recs["decode/mlp"]
    assert mlp.n_kernels >= 1 and mlp.groups
    # prefill buckets compile through stripe_jit too
    assert any(k.startswith("prefill_L") for k in recs)


def test_bucket_cache_counts_real_traffic(model, params):
    eng = _engine(model)
    # lengths 5 and 6 share the 8-bucket; 12 lands in 16
    for r in _mk_requests(model.cfg, [5, 6, 12, 6], new=3):
        eng.submit(r)
    eng.run(params, max_steps=4096)
    stats = eng.cache_stats()
    assert stats.misses >= 2     # two cold buckets (plus decode/stripe keys)
    assert stats.hits >= 2       # repeat admissions hit the bucket entries
    buckets = [e["bucket"] for e in eng.compile_log() if e["kind"] == "prefill"]
    assert sorted(buckets) == [8, 16]


def test_disk_warm_start(model, params, tmp_path):
    def boot():
        cache = stripe_cache.CompilationCache(
            capacity=64, disk_dir=tmp_path, use_disk=True)
        return ServingEngine(
            model, EngineConfig(slots=2, max_len=48, page_size=8),
            compile_cache=cache)

    first = boot()
    for r in _mk_requests(model.cfg, [5, 12], new=3):
        first.submit(r)
    done_a = first.run(params, max_steps=4096)

    second = boot()
    for r in _mk_requests(model.cfg, [5, 12], new=3):
        second.submit(r)
    done_b = second.run(params, max_steps=4096)
    warm = [e for e in second.events() if e["event"] == "warm_start"]
    assert warm and sorted(warm[0]["buckets"]) == [8, 16]
    warm_prefills = [e for e in second.compile_log()
                     if e["kind"] == "prefill" and e.get("warm_start")]
    assert len(warm_prefills) == 2, "manifest buckets should compile at boot"
    assert {r.uid: r.out_tokens for r in done_a} == \
           {r.uid: r.out_tokens for r in done_b}


# ------------------------------------------------------------------- API
def test_streaming_generate(model, params):
    eng = _engine(model)
    prompts = [p.prompt for p in _mk_requests(model.cfg, [4, 9, 6], new=4)]
    stream = list(eng.generate(prompts, params=params,
                               sampling=SamplingParams(max_new_tokens=4)))
    by_uid = {}
    for uid, tok in stream:
        by_uid.setdefault(uid, []).append(tok)
    assert sorted(by_uid) == [0, 1, 2]
    assert all(len(v) == 4 for v in by_uid.values())
    # the stream is the same tokens run() would return
    eng2 = _engine(model)
    for i, p in enumerate(prompts):
        eng2.submit(Request(uid=i, prompt=p,
                            sampling=SamplingParams(max_new_tokens=4)))
    ref = {r.uid: r.out_tokens for r in eng2.run(params, max_steps=4096)}
    assert by_uid == ref


def test_sjf_admission_prefers_short_jobs(model, params):
    eng = _engine(model, slots=1, admission="sjf")
    long_r, short_r = _mk_requests(model.cfg, [32, 4], new=8)
    eng.submit(long_r)
    eng.submit(short_r)
    done = eng.run(params, max_steps=4096)
    assert [r.uid for r in done] == [short_r.uid, long_r.uid], \
        "sjf should serve the short job first despite arrival order"
    # fcfs keeps arrival order
    eng = _engine(model, slots=1, admission="fcfs")
    a, b = _mk_requests(model.cfg, [32, 4], new=8)
    eng.submit(a)
    eng.submit(b)
    assert [r.uid for r in eng.run(params, max_steps=4096)] == [a.uid, b.uid]


def test_legacy_shim(model, params):
    # positional ints, and flat Request fields, as the old engine took
    eng = ServingEngine(model, 2, 48)
    assert eng.slots == 2 and eng.max_len == 48
    r = Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                max_new_tokens=3, eos_id=-1)
    assert r.sampling.max_new_tokens == 3
    eng.submit(r)
    done = eng.run(params, max_steps=64)
    assert len(done) == 1 and len(done[0].out_tokens) == 3


def test_temperature_not_implemented():
    with pytest.raises(NotImplementedError):
        SamplingParams(temperature=0.7).validate()


def test_non_dense_family_rejected(params):
    # routed experts (family "moe") are served; a hybrid state-space
    # family is not yet
    cfg = _tiny_cfg()
    cfg = cfg.scaled(family="hybrid")
    model = build_model(cfg)
    with pytest.raises(ValueError, match="WaveEngine"):
        ServingEngine(model, EngineConfig(slots=2, max_len=32))


# ----------------------------------------------------- resilience contract
def test_submit_accepts_and_statuses_default_ok(model, params):
    # without max_queue/ttl the resilience layer is invisible: submit()
    # returns True, nothing sheds, every request finishes status "ok"
    eng = _engine(model)
    for r in _mk_requests(model.cfg, [4, 9, 6]):
        assert eng.submit(r) is True
    done = eng.run(params, max_steps=4096)
    assert all(r.status == "ok" and r.error == "" and r.retries == 0
               for r in done)
    assert eng.shed() == []
    m = eng.metrics()
    assert m["shed"] == 0 and m["retries"] == 0 and m["quarantined"] == 0
    assert m["finished_by_status"] == {"ok": 3}


def test_bounded_queue_sheds_synchronously(model, params):
    eng = _engine(model, slots=1, max_queue=2)
    reqs = _mk_requests(model.cfg, [4, 5, 6, 7])
    results = [eng.submit(r) for r in reqs]
    # prep drains fast, so at least the request submitted against a full
    # queue is shed; shed requests never reach the engine
    assert results[0] is True
    assert not all(results), "queue of 2 must shed some of 4 rapid submits"
    done = eng.run(params, max_steps=4096)
    shed_uids = {r.uid for r in eng.shed()}
    assert {r.uid for r in done}.isdisjoint(shed_uids)
    assert {r.uid for r in done} | shed_uids == {r.uid for r in reqs}
    for r in eng.shed():
        assert r.status == "shed" and r.done and r.out_tokens == []
