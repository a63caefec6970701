"""repro.obs: span tracing (nesting, thread-safety, Chrome-trace schema),
metrics registry determinism, profiled-compile residual logging, and the
back-compat shims (``cache_stats()`` fields, engine ``metrics()`` dict)."""
import json
import threading
import time

import numpy as np
import pytest

from repro import configs, obs
from repro.core import cache as stripe_cache
from repro.core.driver import stripe_jit
from repro.core.hwconfig import get_config
from repro.models.build import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.__main__ import main as obs_cli
from repro.serving import EngineConfig, Request, SamplingParams, ServingEngine


@pytest.fixture()
def tracer():
    """A fresh, enabled tracer installed as the process default."""
    saved = obs_trace.get_tracer()
    t = obs_trace.Tracer(enabled=True)
    obs_trace.set_tracer(t)
    yield t
    obs_trace.set_tracer(saved)


def _matmul_prog():
    from repro.core.frontend import single_op_program
    return single_op_program(
        "C[i, j] += A[i, k] * B[k, j]",
        {"A": ((32, 16), "float32"), "B": ((16, 24), "float32"),
         "C": ((32, 24), "float32")}, out="C")


# ---------------------------------------------------------------- tracing
def test_span_nesting_and_attrs(tracer):
    with obs_trace.span("outer", kind="a"):
        with obs_trace.span("inner") as sp:
            sp.set(extra=7)
    recs = {r.name: r for r in tracer.spans()}
    assert set(recs) == {"outer", "inner"}
    assert recs["inner"].depth == recs["outer"].depth + 1
    assert recs["inner"].parent == "outer"
    assert recs["inner"].attrs["extra"] == 7
    assert recs["outer"].ts <= recs["inner"].ts
    assert (recs["inner"].ts + recs["inner"].dur
            <= recs["outer"].ts + recs["outer"].dur + 1e-9)


def test_span_records_exceptions(tracer):
    with pytest.raises(ValueError):
        with obs_trace.span("boom"):
            raise ValueError("x")
    (rec,) = tracer.spans()
    assert "error" in rec.attrs


def test_spans_disabled_are_free():
    saved = obs_trace.get_tracer()
    t = obs_trace.Tracer(enabled=False)
    obs_trace.set_tracer(t)
    try:
        with obs_trace.span("nope"):
            pass
        obs_trace.instant("nope2")
        assert t.spans() == []
    finally:
        obs_trace.set_tracer(saved)


def test_span_thread_safety(tracer):
    """Concurrent spans from many threads land without loss and keep
    per-thread nesting (the serving prep thread does exactly this)."""
    n_threads, n_spans = 8, 50

    def worker(i):
        for j in range(n_spans):
            with obs_trace.span(f"w{i}", j=j):
                pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tracer.spans()
    assert len(recs) == n_threads * n_spans
    assert all(r.depth == 0 for r in recs)  # no cross-thread nesting


def test_ring_buffer_bounds_spans():
    t = obs_trace.Tracer(capacity=10, enabled=True)
    for i in range(25):
        with t.span(f"s{i}"):
            pass
    assert len(t.spans()) == 10
    assert t.dropped == 15


def test_chrome_trace_schema(tracer, tmp_path):
    with obs_trace.span("phase.one", tag=1):
        obs_trace.instant("marker")
    now = time.perf_counter()
    obs_trace.span_at("retro", now - 0.25, now, uid=3)
    path = tmp_path / "trace.json"
    obs_trace.get_tracer().export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    phs = {e["ph"] for e in evs}
    assert "X" in phs and "i" in phs and "M" in phs
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] >= 0
    names = {e["name"] for e in evs if e["ph"] in ("X", "i")}
    assert {"phase.one", "marker", "retro"} <= names


def test_cli_summarize(tracer, tmp_path, capsys):
    with obs_trace.span("pass.fuse"):
        pass
    t0 = time.perf_counter()
    obs_trace.span_at("serve.request", t0 - 0.5, t0, uid=0, status="ok",
                      tokens=4)
    obs_trace.span_at("serve.queue", t0 - 0.5, t0 - 0.4, uid=0)
    obs_trace.span_at("serve.prefill", t0 - 0.4, t0 - 0.3, uid=0)
    path = tmp_path / "t.json"
    obs_trace.get_tracer().export_chrome_trace(str(path))
    assert obs_cli(["summarize", str(path), "--requests"]) == 0
    out = capsys.readouterr().out
    assert "pass.fuse" in out and "serve.request" in out
    assert "queue" in out  # the per-request breakdown rendered


def test_request_breakdown():
    events = [
        {"name": "serve.request", "ph": "X", "ts": 0.0, "dur": 1_000_000.0,
         "pid": 1, "tid": 1, "args": {"uid": 5, "status": "ok"}},
        {"name": "serve.queue", "ph": "X", "ts": 0.0, "dur": 300_000.0,
         "pid": 1, "tid": 1, "args": {"uid": 5}},
        {"name": "serve.prefill", "ph": "X", "ts": 300_000.0,
         "dur": 200_000.0, "pid": 1, "tid": 1, "args": {"uid": 5}},
    ]
    per = obs_trace.request_breakdown(events)
    assert per[5]["queue_s"] == pytest.approx(0.3)
    assert per[5]["prefill_s"] == pytest.approx(0.2)
    assert per[5]["decode_s"] == pytest.approx(0.5)
    assert per[5]["total_s"] == pytest.approx(1.0)


# ---------------------------------------------------------------- metrics
def test_metrics_snapshot_deterministic():
    reg = obs_metrics.Registry()
    reg.counter("b.count", route="y").inc(2)
    reg.counter("a.count").inc()
    reg.gauge("a.gauge").set(1.5)
    for v in (0.001, 0.002, 0.004, 0.1):
        reg.histogram("lat").observe(v)
    s1, s2 = reg.snapshot(), reg.snapshot()
    assert s1 == s2
    assert list(s1["counters"]) == sorted(s1["counters"])
    assert s1["counters"]["a.count"] == 1
    assert s1["counters"]["b.count{route=y}"] == 2
    h = s1["histograms"]["lat"]
    assert h["count"] == 4
    assert h["min"] == pytest.approx(0.001)
    assert h["max"] == pytest.approx(0.1)
    assert h["sum"] == pytest.approx(0.107)
    assert 0.001 <= h["p50"] <= h["p99"] <= 0.2 + 1e-9


def test_metrics_type_conflict():
    reg = obs_metrics.Registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_metrics_thread_safety():
    reg = obs_metrics.Registry()
    c = reg.counter("n")

    def bump():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=bump) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40_000


# -------------------------------------------------- cache_stats back-compat
def test_cache_stats_shim_back_compat():
    stats = stripe_cache.CacheStats()
    assert stats.hits == 0
    stats.hits += 3
    stats.misses = 2
    assert (stats.hits, stats.misses) == (3, 2)
    d = stats.as_dict()
    assert d["hits"] == 3 and d["misses"] == 2
    assert set(d) == set(stripe_cache.CacheStats.FIELDS)
    # the counters live in a real registry
    snap = stats.registry.snapshot()
    assert snap["counters"]["cache.hits"] == 3


def test_cache_stats_counts_real_traffic(tmp_path):
    cache = stripe_cache.CompilationCache(disk_dir=str(tmp_path))
    hw = get_config("cpu_test")
    stripe_jit(_matmul_prog(), hw, backend="jnp", cache=cache)
    stripe_jit(_matmul_prog(), hw, backend="jnp", cache=cache)
    assert cache.stats.misses >= 1 and cache.stats.hits >= 1


# ------------------------------------------------------- profiled compiles
def test_profiled_compile_residuals(tmp_path):
    cache = stripe_cache.CompilationCache(disk_dir=str(tmp_path))
    hw = get_config("cpu_test")
    compiled = stripe_jit(_matmul_prog(), hw, backend="jnp", cache=cache,
                          profile=True)
    rec = compiled.record
    assert rec.profiled
    assert rec.predicted_latency_s  # cost model ran
    rng = np.random.RandomState(0)
    ins = {"A": rng.randn(32, 16).astype(np.float32),
           "B": rng.randn(16, 24).astype(np.float32)}
    compiled(ins)
    assert rec.measured_latency_s
    assert all(v > 0 for v in rec.measured_latency_s.values())
    res = rec.latency_residuals()
    assert res and {"block", "predicted_s", "measured_s"} <= set(res[0])
    rows = obs.read_residuals(obs.residual_log_path(cache))
    assert rows, "profiled dispatch must append residual rows"
    for row in rows:
        assert row["measured_s"] > 0
        assert row["ir_fingerprint"] and row["hw_fingerprint"]
    summ = obs.summarize_residuals(rows)
    assert summ["rows"] == len(rows)
    assert summ["pairs_with_prediction"] >= 1
    # a profiled compile must not be served from the unprofiled cache line
    plain = stripe_jit(_matmul_prog(), hw, backend="jnp", cache=cache)
    assert not plain.record.profiled


def test_compile_spans_emitted(tmp_path, tracer):
    cache = stripe_cache.CompilationCache(disk_dir=str(tmp_path))
    stripe_jit(_matmul_prog(), get_config("cpu_test"), backend="jnp",
               cache=cache)
    names = [r.name for r in tracer.spans()]
    assert "compile.stripe_jit" in names
    assert any(n.startswith("pass.") for n in names)
    assert "cache.probe" in names


# --------------------------------------------------------- serving engine
def _tiny_model():
    cfg = configs.get("llama3-8b").scaled(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
        head_dim=16, vocab_pad_multiple=16)
    return cfg, build_model(cfg)


def _run_requests(eng, cfg, params, n=4, base_uid=0):
    r = np.random.RandomState(0)
    for i in range(n):
        eng.submit(Request(uid=base_uid + i,
                           prompt=r.randint(1, cfg.vocab, size=5).astype(np.int32),
                           sampling=SamplingParams(max_new_tokens=4)))
    return eng.run(params, max_steps=10_000)


def test_engine_metrics_shim_and_registry(tracer):
    import jax
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, EngineConfig(slots=2, max_len=32, page_size=8))
    done = _run_requests(eng, cfg, params)
    assert len(done) == 4

    m = eng.metrics()  # legacy dict shape, plus dropped_events
    for key in ("decode_steps", "tokens_out", "finished", "slot_utilization",
                "queue_depth", "dropped_events"):
        assert key in m
    assert m["finished"] == 4 and m["dropped_events"] == 0

    snap = eng.metrics_snapshot()
    assert snap["counters"]["serve.tokens_out"] == m["tokens_out"]
    assert snap["counters"]["serve.finished{status=ok}"] == 4
    assert snap["counters"]["serve.events{event=admit}"] == 4
    assert snap["histograms"]["serve.request_s"]["count"] == 4
    assert snap["histograms"]["serve.decode_step_s"]["count"] == m["decode_steps"]
    assert snap["histograms"]["serve.queue_wait_s"]["count"] == 4
    assert snap["histograms"]["serve.prefill_s"]["count"] == 4

    # request-lifecycle spans: queue + prefill + request per uid, decode steps
    names = [r.name for r in tracer.spans()]
    assert names.count("serve.request") == 4
    assert names.count("serve.queue") == 4
    assert names.count("serve.prefill") == 4
    assert "serve.decode_step" in names
    assert "serve.prep" in names  # recorded on the prep thread


def test_engine_event_ring_buffer():
    import jax
    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, EngineConfig(slots=2, max_len=32, page_size=8,
                                            event_log_size=5))
    _run_requests(eng, cfg, params, n=4)
    assert len(eng.events()) == 5
    assert eng.metrics()["dropped_events"] > 0
    # the registry still counted every event, drops notwithstanding
    snap = eng.metrics_snapshot()
    assert snap["counters"]["serve.events{event=finish}"] == 4


# ------------------------------------------------- the profiler's clock
@pytest.fixture()
def annotations(monkeypatch):
    """Every ``jax.profiler.TraceAnnotation`` made, as (name, kwargs)."""
    import jax.profiler

    made = []

    class Counting:
        def __init__(self, name, **kw):
            made.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    return made


def test_disabled_tracing_makes_no_annotation(annotations):
    t = obs_trace.Tracer(enabled=False)
    for i in range(100):
        with t.span("serve.decode_step", step=i, n_live=3):
            pass
    with t.timed("serve.setup.decode") as sp:
        time.sleep(0.002)
    assert annotations == []
    assert t.spans() == []
    assert t.span("serve.emit", step=1) is obs_trace._NULL
    assert sp.dur >= 0.002  # a timed span measures even when off


def test_enabled_span_is_annotated_with_its_ids(annotations, tracer):
    with obs_trace.span("serve.prefill", uid=7, bucket=64, slot=2, step=[1]):
        with obs_trace.timed("serve.setup.prefill", bucket=64) as sp:
            sp.set(cache="miss")
    obs_trace.span_at("serve.queue", 0.0, 1.0, uid=7)
    obs_trace.instant("serve.admit", uid=7)
    # only live spans are annotated, and only with scalar step/uid/bucket
    assert annotations == [("serve.prefill", {"uid": 7, "bucket": 64}),
                           ("serve.setup.prefill", {"bucket": 64})]
    recs = {r.name: r for r in tracer.spans() if r.phase == "X"}
    assert recs["serve.setup.prefill"].dur == sp.dur
    assert recs["serve.setup.prefill"].attrs == {"bucket": 64, "cache": "miss"}
    assert recs["serve.setup.prefill"].parent == "serve.prefill"


def test_spans_reach_a_profiler_trace(tracer, tmp_path):
    """A span taken while the profiler runs is a host event of the same
    name, on the thread that ran it, with its ids as stats."""
    import glob
    import os

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("serve.decode_step", step=5, n_live=3):
            with obs_trace.span("serve.decode.sync", step=5):
                jax.block_until_ready(jnp.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    got = {}
    for plane in pd.planes:
        for i, ln in enumerate(plane.lines):
            for e in ln.events:
                if e.name.startswith("serve."):
                    got[e.name] = (plane.name, i, dict(e.stats),
                                   e.start_ns, e.start_ns + e.duration_ns)
    assert set(got) == {"serve.decode_step", "serve.decode.sync"}
    step, sync = got["serve.decode_step"], got["serve.decode.sync"]
    assert step[:2] == sync[:2]  # one thread's line
    assert {int(v) for v in step[2].values()} == {5} and "n_live" not in step[2]
    assert step[3] <= sync[3] and sync[4] <= step[4]


# every serve.* span of the loop, with the parent it must have
SPAN_PARENTS = {
    "serve.admit": {""},
    "serve.prep_wait": {"serve.admit"},
    "serve.prefill": {"serve.admit"},
    "serve.decode_step": {""},
    "serve.decode.dispatch": {"serve.decode_step"},
    "serve.decode.sync": {"serve.decode_step"},
    "serve.emit": {""},
    "serve.setup.decode": {""},
    "serve.setup.prefill": {"serve.admit", "serve.setup.warm_start"},
    "serve.setup.warm_decode": {""},
    "serve.setup.warm_start": {""},
}


def test_engine_span_tree(tracer, tmp_path):
    """A tiny engine, booted twice over one disk cache (the second boot
    replays the bucket manifest), records every phase of its loop under
    the parent the loop gives it; each compile's log entry is timed by
    its setup span."""
    import jax

    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))

    def boot():
        cache = stripe_cache.CompilationCache(capacity=64, disk_dir=tmp_path,
                                              use_disk=True)
        eng = ServingEngine(model, EngineConfig(slots=2, max_len=32, page_size=8),
                            compile_cache=cache)
        real = eng._prepare

        def slow(req):  # a warm engine's admission waits for the prep thread
            time.sleep(0.05)
            return real(req)

        eng._prepare = slow
        return eng

    first = boot()
    assert len(_run_requests(first, cfg, params)) == 4
    assert len(_run_requests(first, cfg, params, n=1, base_uid=4)) == 1
    second = boot()
    assert len(_run_requests(second, cfg, params, base_uid=5)) == 4

    spans = [r for r in tracer.spans()
             if r.phase == "X" and r.name in SPAN_PARENTS]
    parents = {}
    for r in spans:
        parents.setdefault(r.name, set()).add(r.parent)
    assert set(parents) == set(SPAN_PARENTS)
    for name, got in parents.items():
        assert got <= SPAN_PARENTS[name], (name, got)
    caches = {r.attrs["cache"] for r in spans if r.name == "serve.setup.prefill"}
    assert caches == {"hit", "miss"}
    steps = [r for r in spans if r.name == "serve.decode_step"]
    assert len(steps) == first.metrics()["decode_steps"] + \
        second.metrics()["decode_steps"]

    # one measurement per compile: the log's time is the span's duration
    misses = sorted(r.dur for r in spans
                    if r.name == "serve.setup.prefill" and r.attrs["cache"] == "miss")
    logged = sorted(e["first_call_s"] for eng in (first, second)
                    for e in eng.compile_log() if e["kind"] == "prefill")
    assert logged == misses
    warm = [r.dur for r in spans if r.name == "serve.setup.warm_decode"]
    assert sorted(warm) == sorted(e["first_call_s"] for eng in (first, second)
                                  for e in eng.compile_log() if e["kind"] == "decode")


def test_prefill_histogram_excludes_the_compile(tracer):
    """A bucket compiled on admission is set-up time: the prefill's own
    span and ``serve.prefill_s`` start after the program is fetched."""
    import jax

    cfg, model = _tiny_model()
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, EngineConfig(slots=2, max_len=32, page_size=8))
    _run_requests(eng, cfg, params, n=1)
    (miss,) = [r for r in tracer.spans() if r.name == "serve.setup.prefill"]
    (prefill,) = [r for r in tracer.spans()
                  if r.name == "serve.prefill" and r.phase == "X"]
    assert miss.attrs["cache"] == "miss"
    assert miss.ts + miss.dur <= prefill.ts
    hist = eng.metrics_snapshot()["histograms"]["serve.prefill_s"]
    assert hist["count"] == 1 and hist["sum"] < miss.dur
