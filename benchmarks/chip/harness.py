#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/harness.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name: the cell
in ``BENCHMARK.json`` at the root of the checkout, the configuration in
``configs/<config>.json`` (with its plain reference, ``configs/<reference>.py``),
the mix in ``traffic/<mix>.json`` and each metric in ``metrics/<metric>.py``.

A run loads the served model with weights made on the device from the
seed, warms up every program the mix uses, serves the mix through
``repro.api.ServingEngine`` with the platform's defaults for ``--seconds``,
then checks the served tokens against the plain reference.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
it traces part of the window and reports the per-layer metrics.  The last
line of standard output is one JSON object.  A run that finds no TPU, too
few chips, or a chip whose peaks are not in ``peaks.json`` exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE = os.path.join(ROOT, ".bench_cache")
sys.path.insert(0, HERE)

# the configuration's widths, checked against the served registry entry
_WIDTHS = {"n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
           "n_kv_heads": "n_kv_heads", "head_dim": "hd", "d_ff": "d_ff",
           "vocab": "vocab", "tie_embeddings": "tie_embeddings",
           "qk_norm": "qk_norm", "rope_theta": "rope_theta", "dtype": "dtype",
           "act": "act"}


class NoChip(RuntimeError):
    """The machine cannot run this cell: no result is printed."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, conf


def cell_metrics(bench: dict, cell_name: str, trace: bool):
    """The metrics this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def check_device(jax, chips: int, require_tpu: bool = True, kind=None):
    """The devices, their kind's peaks; refuses what cannot run the cell.
    ``kind`` stands in for the device's kind where no chip is attached."""
    import yardstick

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform {dev.platform}, device_kind {dev.device_kind}, "
          f"count {len(devs)}", flush=True)
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {dev.platform} devices only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    try:
        pk = yardstick.peaks(kind or dev.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devs, pk


def check_widths(model: dict, cfg) -> None:
    """The configuration file and the served registry entry agree."""
    for key, attr in _WIDTHS.items():
        got = getattr(cfg, attr)
        if key == "tie_embeddings" or key == "qk_norm":
            ok = bool(got) == bool(model[key])
        elif isinstance(got, float):
            ok = abs(got - float(model[key])) <= 1e-9 * abs(got)
        else:
            ok = got == model[key]
        if not ok:
            raise SystemExit(f"config {cfg.name}: {key} is {model[key]!r} in the "
                             f"configuration file, {got!r} in the registry")
    rot = cfg.hd if cfg.rope == "full" else cfg.hd // 2
    if model["rotary_dims"] != rot:
        raise SystemExit(f"config {cfg.name}: rotary_dims {model['rotary_dims']} "
                         f"!= served {rot}")


def seed_key(jax, seed: int):
    """A JAX key from any whole-number seed, however large."""
    import numpy as np

    state = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jax.numpy.asarray(state, jax.numpy.uint32),
                                    impl="threefry2x32")


class Setup:
    """Model, weights and a warmed engine for one cell."""

    def __init__(self, cell: dict, conf: dict, seed: int, require_tpu: bool = True,
                 kind=None):
        import jax

        import serve
        import traffic
        from repro import api

        self.jax, self.api = jax, api
        self.cell = cell
        self.spec = load_json(ROOT, conf["file"])
        self.model = self.spec["model"]
        self.mix = traffic.load(cell["traffic"])
        api.enable_compilation_cache(ROOT)
        self.devs, self.peaks = check_device(jax, int(cell["chips"]), require_tpu, kind)
        self.ref = load_module(os.path.join(HERE, "configs", self.spec["reference"] + ".py"),
                               "reference_" + self.spec["reference"])
        cfg = api.configs.get(self.spec["registry"])
        if "scaled" in self.spec:  # a small stand-in, for tests on the CPU
            cfg = cfg.scaled(**self.spec["scaled"])
        check_widths(self.model, cfg)
        self.cfg = cfg
        self.built = api.build_model(cfg)
        self.seed = seed
        shapes = jax.eval_shape(self.built.init, jax.random.PRNGKey(0))
        self.params = jax.block_until_ready(
            self.ref.make_weights(shapes, seed_key(jax, seed)))
        geo = self.spec["engine"]
        self.cache = api.CompilationCache(disk_dir=os.path.join(CACHE, "stripe"))
        self.engine = api.ServingEngine(
            self.built, api.EngineConfig(slots=geo["slots"], max_len=geo["max_len"],
                                         page_size=geo["page_size"]),
            compile_cache=self.cache)
        ec = self.engine.config
        print(f"engine: backend {ec.backend}, interpret {ec.interpret}, hw {ec.hw}, "
              f"{ec.slots} slots, max_len {ec.max_len}, page_size {ec.page_size}",
              flush=True)
        if require_tpu and (ec.backend != "pallas" or ec.interpret):
            raise SystemExit("the engine does not default to compiled Pallas on a TPU")
        self.buckets = traffic.prompt_buckets(self.mix, ec.max_len)
        serve.warm_up(api, self.engine, self.params, self.buckets, self.model["vocab"],
                      first_uid=1 << 30)
        self.compiles = 0

        def count(event, *_a, **_k):
            if event.startswith("/jax/core/compile/"):
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(count)

    def records(self):
        """Backends of the decode programs, as the compile records give them."""
        out = {}
        for name, rec in self.engine.compile_records().items():
            if name.startswith("decode/"):
                out[name[len("decode/"):]] = {
                    "backend": rec.backend, "n_kernels": rec.n_kernels,
                    "all_pallas": rec.backend == "pallas" and set(
                        rec.block_backends.values()) == {"pallas"}}
        return out

    def free_engine(self):
        if self.engine is not None:
            self.engine.close()
            self.engine = None
        gc.collect()


class Run:
    """What the metric readers read: the window and its setting."""

    def __init__(self, setup: Setup, window, setup_s: float, records):
        self.window = window
        self.seconds = window.t_end - window.t0
        self.setup_s = setup_s
        self.model = setup.model
        self.engine = setup.spec["engine"]
        self.peaks = setup.peaks
        self.decode_records = records

    def hist_delta(self, name):
        (c0, s0), (c1, s1) = self.window.hist0[name], self.window.hist1[name]
        return c1 - c0, s1 - s0

    def decode_contexts(self):
        """Positions attended by each decode token emitted in the window
        (the prefill's first token is not a decode token)."""
        w, out = self.window, []
        for r in w.records.values():
            n = len(r.prompt)
            for j, t in enumerate(r.times):
                if j > 0 and w.t0 <= t <= w.t_end:
                    out.append(n + j)
        return out


def read_metrics(specs, run, trace):
    out = {}
    for spec in specs:
        mod = load_module(os.path.join(HERE, "metrics", spec["name"] + ".py"),
                          "metric_" + spec["name"].replace(".", "_"))
        v = mod.read(run, trace)
        if v is not None:
            out[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    return out


def served_sample(setup: Setup, window, seed: int):
    """A seeded sample of the requests that finished: the longest, then
    others in an order drawn from the seed until the sample holds
    ``sample_tokens`` served tokens."""
    import numpy as np

    import traffic

    done = sorted((r for r in window.records.values() if r.status == "ok"),
                  key=lambda r: (-len(r.tokens), r.uid))
    if not done:
        return []
    rng = traffic.rng_for(seed, 7)
    rest = [done[i] for i in rng.permutation(np.arange(1, len(done)))]
    sample = [done[0]]
    while rest and sum(len(r.tokens) for r in sample) < setup.spec["correct"]["sample_tokens"]:
        sample.append(rest.pop(0))
    return sample


def check_served(setup: Setup, window, seed: int, control=None):
    """Compare the sample of finished requests with the plain reference.
    Returns (correct, {number: (value, limit)}, the control's widest gap
    or None)."""
    import numpy as np

    lim = setup.spec["correct"]
    vocab = setup.model["vocab"]
    done = [r for r in window.records.values() if r.status == "ok"]
    bad_len = sum(1 for r in done if len(r.tokens) != r.max_new)
    bad_id = sum(1 for r in done for t in r.tokens if not 0 <= t < vocab)
    sample = served_sample(setup, window, seed)
    n_tok = sum(len(r.tokens) for r in sample)
    gap = ctrl = None
    if sample and not bad_id:
        got = setup.ref.served_gaps(setup.params, setup.model,
                                    [r.prompt for r in sample],
                                    [r.tokens for r in sample], control=control)
        if control is not None:
            got, c = got
            ctrl = float(np.max(c))
        gap = float(np.max(got))
    nums = {"finished_wrong_length": (bad_len, 0), "token_ids_out_of_vocab": (bad_id, 0),
            "tokens_compared": (n_tok, lim["sample_tokens"]),
            "max_logit_gap": (gap, lim["max_logit_gap"])}
    ok = (bad_len == 0 and bad_id == 0 and n_tok >= lim["sample_tokens"]
          and gap is not None and gap <= lim["max_logit_gap"])
    return ok, nums, ctrl


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, bench=None, t_start: float = None, kind=None):
    """One run; returns the result dictionary (the last line's object)."""
    import serve

    bench = bench if bench is not None else load_json(ROOT, "BENCHMARK.json")
    cell, conf = find_cell(bench, workload)
    t_start = T_START if t_start is None else t_start
    setup = Setup(cell, conf, seed, require_tpu, kind)
    tracer = None
    if trace:
        import tracereduce

        tracer = tracereduce.Tracer(os.path.join(CACHE, "trace", workload), seconds)
    opened = {}

    def on_open(t0):
        opened["setup_s"] = t0 - t_start
        if tracer is not None:
            tracer.open(t0)

    window = serve.run_window(
        setup.api, setup.engine, setup.params, setup.mix, seed, seconds,
        setup.model["vocab"], lambda: setup.compiles, on_open=on_open,
        on_tick=tracer.tick if tracer else None)
    print(f"window: {len(window.records)} requests submitted, compiles inside the "
          f"window: {window.compiles}", flush=True)
    if window.late_s:
        print(f"generator lateness: mean {sum(window.late_s) / len(window.late_s):.6f} s, "
              f"max {max(window.late_s):.6f} s", flush=True)
    records = setup.records()
    stats = setup.devs[0].memory_stats() or {}
    device = {"platform": setup.devs[0].platform, "kind": setup.devs[0].device_kind,
              "count": len(setup.devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    reduced = None
    if tracer is not None:
        reduced = tracer.reduce()
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    run = Run(setup, window, opened["setup_s"], records)
    metrics = read_metrics(cell_metrics(bench, workload, trace), run, reduced)
    setup.free_engine()
    ok, nums, _ = check_served(setup, window, seed)
    attempted = len(window.records)
    failed = sum(1 for r in window.records.values()
                 if (r.status and r.status != "ok") or
                 (r.due is not None and r.due < window.t_end and not r.times))
    out = {"correct": bool(ok), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = reduced.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in nums.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"harness: {e}", file=sys.stderr)
        return 2
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
