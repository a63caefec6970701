#!/usr/bin/env python3
"""Readings that the benchmark's limits and rates are set from, many
seeds to one process (the benchmark's own runs never do this).

    # the check's two readings: served tokens against the reference (the
    # program) and the fp8 control's first choices against the reference
    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--control fp8]

    # the knee of an open-loop mix: one window per rate
    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11 --rates 2,3,4

Each seed gets its own weights and traffic and a fresh engine over the
programs compiled once; each window is served exactly as a run serves
it.  One JSON line per seed or rate goes to standard output, and all of
them to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import harness


def one_window(setup, seed, seconds, mix, drain_s=60.0):
    import serve

    setup.engine = setup.api.ServingEngine(
        setup.built, setup.api.EngineConfig(**setup.spec["engine"]),
        compile_cache=setup.cache)
    serve.warm_up(setup.api, setup.engine, setup.params, setup.buckets,
                  setup.model["vocab"], first_uid=1 << 30)
    return serve.run_window(setup.api, setup.engine, setup.params, mix, seed,
                            seconds, setup.model["vocab"], lambda: setup.compiles,
                            drain_s=drain_s)


def main(argv=None, bench=None, **setup_kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import numpy as np

    bench = bench or harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, conf = harness.find_cell(bench, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    setup = harness.Setup(cell, conf, seeds[0], **setup_kw)
    jax = setup.jax
    shapes = jax.eval_shape(setup.built.init, jax.random.PRNGKey(0))
    rows = []
    if args.rates:
        for rate in [float(r) for r in args.rates.split(",")]:
            setup.free_engine()
            mix = dict(setup.mix, rate_per_s=rate)
            w = one_window(setup, seeds[0], args.seconds, mix, drain_s=0.0)
            recs = list(w.records.values())
            started = [r for r in recs if r.times and r.times[0] <= w.t_end]
            ttft = sorted(r.times[0] - r.due for r in started)
            # requests waiting for their first token at 1-second marks
            marks = np.arange(w.t0 + 1.0, w.t_end, 1.0)
            queued = [int(sum(1 for r in recs if r.submit <= t and
                              not (r.times and r.times[0] <= t))) for t in marks]
            half = len(queued) // 2
            slope = (float(np.polyfit(marks[half:] - w.t0, queued[half:], 1)[0])
                     if len(queued) - half >= 2 else None)
            row = {"rate": rate, "submitted": len(recs), "started": len(started),
                   "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)) if ttft else None,
                   "queued_each_s": queued, "queue_slope_per_s": slope}
            print(json.dumps(row), flush=True)
            rows.append(row)
    else:
        for seed in seeds:
            t = time.perf_counter()
            setup.free_engine()
            setup.params = None
            setup.params = jax.block_until_ready(
                setup.ref.make_weights(shapes, harness.seed_key(jax, seed)))
            w = one_window(setup, seed, args.seconds, setup.mix)
            setup.free_engine()
            ok, nums, ctrl = harness.check_served(setup, w, seed, control=args.control)
            row = {"seed": seed, "correct": ok, "compiles_in_window": w.compiles,
                   "numbers": {k: v for k, (v, _) in nums.items()},
                   "control_max_logit_gap": ctrl}
            row["seconds"] = time.perf_counter() - t
            print(json.dumps(row), flush=True)
            rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seconds": args.seconds,
                       "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
