"""Traffic benchmark: continuous-batching vs wave serving under load.

An open-loop driver replays a Poisson arrival process (a feeder thread
submits each request at its arrival time while the engine serves) of
``--requests`` mixed-length prompts against both engines at equal slot
count, then reports throughput (tokens/s), request-latency percentiles
(p50/p99, measured submit -> finish per request, so queueing delay under
load is included), and slot utilization.

Both engines are warmed on a throwaway request set before the timed run,
so the comparison is steady-state serving; cold-boot cost is the
compile-cache warm-start story (``ServingEngine.compile_log()``).

``--faults`` runs the chaos leg instead: the same Poisson trace is
replayed through two identical continuous engines — one clean, one under
an injected fault plan spanning five fault classes (prefill-compile
crash, torn disk-cache writes, device-step errors, prep-thread death,
page-allocation failure) — asserting that every request still completes
with *exactly-once, token-identical* output, that every injected fault is
matched by a recovery/degradation event in ``engine.events()``, and that
faulted throughput stays within 70% of fault-free.

``--trace OUT.json`` records the run as spans (request lifecycle, decode
steps, prep work) and writes a Chrome/Perfetto trace; with tracing on,
the continuous leg also reports the mean per-request latency breakdown
(queue wait vs prefill vs decode) computed from those spans.
``--profile`` compiles the engine's Stripe decode programs with
``profile=True`` (per-unit measured latencies + cost-model residual rows).

    PYTHONPATH=src python benchmarks/serve_traffic.py --requests 1000
    PYTHONPATH=src python benchmarks/serve_traffic.py --json OUT.json
    PYTHONPATH=src python benchmarks/serve_traffic.py --faults --json OUT.json
    PYTHONPATH=src python benchmarks/serve_traffic.py --trace trace.json
"""
import argparse
import json
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Dict, List

import jax
import numpy as np

from repro import api, obs
from repro.core.cache import CompilationCache
from repro.reliability import faults


def make_requests(cfg, n: int, seed: int, rate: float, base_uid: int = 0):
    """(arrival_offsets, requests): Poisson arrivals at ``rate`` req/s,
    prompt lengths mixed over [4, 48], generation lengths over [4, 24]."""
    r = np.random.RandomState(seed)
    arrivals = np.cumsum(r.exponential(1.0 / rate, size=n)) if rate > 0 \
        else np.zeros(n)
    reqs = []
    for i in range(n):
        plen = int(r.choice([4, 8, 16, 24, 32, 48]))
        new = int(r.randint(4, 25))
        reqs.append(api.Request(
            uid=base_uid + i,
            prompt=r.randint(1, cfg.vocab, size=plen).astype(np.int32),
            sampling=api.SamplingParams(max_new_tokens=new)))
    return arrivals, reqs


def drive(eng, params, arrivals, reqs) -> Dict[str, Any]:
    """Open-loop run: feeder thread submits on the arrival clock; the
    serve loop drains until every request finished."""
    n = len(reqs)
    done: List[Any] = []
    t0 = time.perf_counter()

    def feeder():
        for arr, r in zip(arrivals, reqs):
            lag = arr - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
            eng.submit(r)

    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    while len(done) < n:
        done.extend(eng.run(params, max_steps=1_000_000))
        if len(done) < n:
            time.sleep(0.0005)
    wall = time.perf_counter() - t0
    th.join()
    toks = sum(len(r.out_tokens) for r in done)
    lats = np.sort([r.finish_time - r.submit_time for r in done])
    return {
        "finished": len(done),
        "tokens": toks,
        "wall_s": round(wall, 3),
        "tok_per_s": round(toks / wall, 1),
        "p50_s": round(float(lats[int(0.50 * n)]), 4),
        "p99_s": round(float(lats[int(0.99 * n)]), 4),
        "slot_utilization": (round(eng.metrics()["slot_utilization"], 3)
                             if isinstance(eng, api.ServingEngine) else None),
    }


def span_breakdown() -> Dict[str, Any]:
    """Mean per-request latency breakdown (queue/prefill/decode seconds)
    from the serving spans currently in the default tracer."""
    events = obs.get_tracer().chrome_trace()["traceEvents"]
    per = obs.trace.request_breakdown(events)
    if not per:
        return {}

    def mean(k):
        return sum(r[k] for r in per.values()) / len(per)

    return {"requests": len(per),
            "queue_s": round(mean("queue_s"), 5),
            "prefill_s": round(mean("prefill_s"), 5),
            "decode_s": round(mean("decode_s"), 5),
            "total_s": round(mean("total_s"), 5)}


def _fault_plan(args) -> faults.FaultPlan:
    """Five fault classes against the timed trace.  Decode-step hits are
    spread through the run; the compile/cache classes land on the buckets
    that (deliberately) were not warmed."""
    return faults.FaultPlan([
        faults.fail_nth("serve.prefill_compile", 1),          # compile crash
        faults.fail_nth("cache.disk_write_torn", 2),          # cache corruption
        faults.fail_nth("cache.disk_write_torn", 5),
        faults.fail_nth("serve.decode_step", 80),             # device errors
        faults.fail_nth("serve.decode_step", 400),
        faults.fail_nth("serve.decode_step", 900),
        faults.fail_nth("serve.prep_thread", args.requests // 2),  # thread death
        faults.fail_nth("paged.alloc", 40),                   # alloc failure
    ])


def bench_faults(args, cfg, model, params) -> Dict[str, Any]:
    """The chaos leg: identical trace through a clean and a faulted
    engine; asserts completion, exactly-once token parity, fault->event
    matching, and >= 70% of fault-free throughput."""
    def mk_engine():
        return api.ServingEngine(
            model, api.EngineConfig(slots=args.slots, max_len=args.max_len,
                                    page_size=args.page_size,
                                    quarantine_backoff_s=0.25),
            compile_cache=CompilationCache(disk_dir=tempfile.mkdtemp(
                prefix="stripe-chaos-")))

    def warm(eng):
        # warm only the short buckets: the long ones compile during the
        # timed run (identically in both legs), giving the compile/cache
        # fault classes real work to corrupt
        r = np.random.RandomState(1)
        for i, plen in enumerate([4, 8, 16] * 2):
            eng.submit(api.Request(
                uid=1_000_000 + i,
                prompt=r.randint(1, cfg.vocab, size=plen).astype(np.int32),
                sampling=api.SamplingParams(max_new_tokens=4)))
        eng.run(params, max_steps=1_000_000)

    results: Dict[str, Any] = {}
    tokens: Dict[str, Dict[int, List[int]]] = {}
    statuses: Dict[str, Dict[int, str]] = {}
    plan = _fault_plan(args)
    for label in ("nofault", "faulted"):
        eng = mk_engine()
        warm(eng)
        arrivals, reqs = make_requests(cfg, args.requests, seed=7, rate=args.rate)
        if label == "faulted":
            with faults.inject(plan):
                res = drive(eng, params, arrivals, reqs)
        else:
            res = drive(eng, params, arrivals, reqs)
        tokens[label] = {r.uid: list(r.out_tokens) for r in reqs}
        statuses[label] = {r.uid: r.status for r in reqs}
        if label == "faulted":
            ev_counts: Dict[str, int] = {}
            for e in eng.events():
                ev_counts[e["event"]] = ev_counts.get(e["event"], 0) + 1
            qs = eng.cache_stats()
            res["faults_injected"] = plan.fired_counts()
            res["recovery_events"] = {
                k: v for k, v in ev_counts.items()
                if k in ("quarantine", "quarantine_expired", "quarantine_clear",
                         "device_step_failed", "requeue", "prep_thread_restart",
                         "alloc_failed", "cache_corruption_recovered",
                         "retry_exhausted", "prep_failed")}
            res["quarantine_stats"] = {
                "quarantined": qs.quarantined, "hits": qs.quarantine_hits,
                "expiries": qs.quarantine_expiries, "clears": qs.quarantine_clears}
            res["retries"] = eng.metrics()["retries"]

            # ---- every injected fault matches a recovery/degradation event
            fired = plan.fired_counts()
            ev = res["recovery_events"]
            assert fired.get("serve.prefill_compile", 0) == ev.get("quarantine", 0)
            assert fired.get("serve.decode_step", 0) == ev.get("device_step_failed", 0)
            assert fired.get("serve.prep_thread", 0) == ev.get("prep_thread_restart", 0)
            assert fired.get("paged.alloc", 0) == ev.get("alloc_failed", 0)
            torn = fired.get("cache.disk_write_torn", 0)
            recovered = sum(e.get("count", 0) for e in eng.events()
                            if e["event"] == "cache_corruption_recovered")
            assert torn == recovered, f"{torn} torn writes, {recovered} recovered"
            assert len(fired) >= 4, f"need >=4 distinct fault classes, got {fired}"
            # quarantine entry + backoff expiry visible via cache_stats()
            assert qs.quarantined >= 1 and qs.quarantine_expiries >= 1
        results[label] = res
        print(f"{label:11s}: {res['tok_per_s']:8.0f} tok/s  "
              f"p50 {res['p50_s']*1e3:7.1f} ms  p99 {res['p99_s']*1e3:7.1f} ms  "
              f"util {res['slot_utilization']}")

    # ---- exactly-once, token-identical completion under faults
    assert statuses["faulted"] == statuses["nofault"], \
        "fault recovery must not change any request's outcome"
    assert all(s == "ok" for s in statuses["faulted"].values())
    diverged = [u for u in tokens["nofault"]
                if tokens["faulted"][u] != tokens["nofault"][u]]
    assert not diverged, f"{len(diverged)} requests diverged under faults: {diverged[:5]}"
    ratio = results["faulted"]["tok_per_s"] / results["nofault"]["tok_per_s"]
    results["faulted_throughput_ratio"] = round(ratio, 3)
    print(f"faulted vs fault-free: {ratio:.2f}x throughput "
          f"({len(results['faulted']['faults_injected'])} fault classes, "
          f"all {args.requests} requests exactly-once)")
    assert ratio >= 0.70, f"faulted throughput {ratio:.2f}x < 0.70x fault-free"
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--rate", type=float, default=250.0,
                    help="Poisson arrival rate, req/s (0 = all queued at t=0)")
    ap.add_argument("--json", default=None)
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record spans and write a Chrome/Perfetto trace; "
                         "also reports the span-derived per-request latency "
                         "breakdown for the continuous engine")
    ap.add_argument("--profile", action="store_true",
                    help="compile the engine's Stripe decode programs with "
                         "profile=True (measured per-unit latencies + "
                         "cost-model residual rows)")
    ap.add_argument("--faults", action="store_true",
                    help="run the chaos leg (fault injection) instead of "
                         "the continuous-vs-wave comparison")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the continuous-beats-wave assertions")
    args = ap.parse_args(argv)
    if args.trace:
        obs.enable_tracing()

    cfg = api.configs.get("llama3-8b").scaled(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab=97, dtype="float32")
    model = api.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    results: Dict[str, Any] = {"config": vars(args)}
    if args.faults:
        results.update(bench_faults(args, cfg, model, params))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(results, f, indent=2)
            print(f"# wrote {args.json}")
        if args.trace:
            obs.export_chrome_trace(args.trace)
            print(f"# wrote {args.trace} ({len(obs.spans())} spans)")
        return

    engines = (
        ("continuous", api.ServingEngine(model, api.EngineConfig(
            slots=args.slots, max_len=args.max_len, page_size=args.page_size,
            profile=args.profile))),
        ("wave", api.WaveEngine(model, args.slots, args.max_len)),
    )
    for label, eng in engines:
        # warm-up: compile every prompt bucket off the clock
        _, warm = make_requests(cfg, 50, seed=1, rate=0.0, base_uid=1_000_000)
        for r in warm:
            eng.submit(r)
        eng.run(params, max_steps=1_000_000)

        if args.trace and label == "continuous":
            obs.clear_trace()  # keep warm-up spans out of the breakdown
        arrivals, reqs = make_requests(cfg, args.requests, seed=7, rate=args.rate)
        res = drive(eng, params, arrivals, reqs)
        if args.trace and label == "continuous":
            bd = res["latency_breakdown"] = span_breakdown()
            if bd:
                print(f"continuous latency breakdown (mean over "
                      f"{bd['requests']} requests): "
                      f"queue {bd['queue_s']*1e3:.1f} ms, "
                      f"prefill {bd['prefill_s']*1e3:.1f} ms, "
                      f"decode {bd['decode_s']*1e3:.1f} ms")
        results[label] = res
        print(f"{label:11s}: {res['tok_per_s']:8.0f} tok/s  "
              f"p50 {res['p50_s']*1e3:7.1f} ms  p99 {res['p99_s']*1e3:7.1f} ms  "
              f"util {res['slot_utilization']}")

    c, w = results["continuous"], results["wave"]
    results["speedup_tok_per_s"] = round(c["tok_per_s"] / w["tok_per_s"], 2)
    results["p99_improvement"] = round(w["p99_s"] / c["p99_s"], 2)
    print(f"continuous vs wave: {results['speedup_tok_per_s']}x throughput, "
          f"{results['p99_improvement']}x better p99")
    if not args.no_check:
        assert c["tok_per_s"] > w["tok_per_s"], "continuous must beat wave on throughput"
        assert c["p99_s"] < w["p99_s"], "continuous must beat wave on p99"
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {args.json}")
    if args.trace:
        obs.export_chrome_trace(args.trace)
        print(f"# wrote {args.trace} ({len(obs.spans())} spans)")


if __name__ == "__main__":
    api.enable_compilation_cache(Path(__file__).resolve().parents[1])
    main()
