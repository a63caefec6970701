#!/usr/bin/env python3
"""Smoke test of the main path on TPU chips.

    python chip_smoke.py              # qwen3-4b served on one chip
    python chip_smoke.py --chips 4    # stripe_jit(mesh=4) on four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny [--chips 4]
                                      # control-flow rehearsal, exits 1

One chip: ``repro.api.build_model`` -> ``ServingEngine`` -> ``stripe_jit``
programs with the engine's platform defaults (compiled Pallas kernels on
a TPU).  Qwen3-4B at its published widths with random bf16 weights from
``--seed``; two rounds of 8 greedy requests (prompts of 97-128 and
400-512 tokens, two prefill buckets), 32 tokens each.  Every served
matmul weight must show in its program's ``CompileRecord.stored_reads``
as read in place (and, for a bf16 configuration, as narrow).  Then each
of the five decode-step programs is checked on the chip against
``jnp.einsum`` in f32 at ``HIGHEST`` precision on seeded inputs at the
served widths, each weight handed as the engine hands it: stacked in the
stored dtype, with the index of the slice to read, and the keys and
values of the two paged attention programs as a page pool with a page
table and slot lengths, every row past a slot's length NaN.  The share
of the KV window the decode steps read (``serve.kv.pages_read /
pages_window``) is printed once, and no donated KV pool may have been
lost to a failed step (``serve.kv.pool_rebuilds`` reads 0).

Four chips: the multi-device compile path alone, i.e. an output-split
SiLU-GLU FFN at Qwen3-4B MLP widths (all_gather) and a reduction-split
matmul (psum), both with Pallas kernels in each shard, compared with the
single-device compile and with ``jnp`` at ``HIGHEST`` precision.

Any failed check exits non-zero.  With no TPU the script exits non-zero
and prints no result: ``--tiny`` runs ``CONFIG.scaled()`` to rehearse the
control flow on the CPU (Pallas in interpret mode) and still fails at
the end.  On success the last line of standard output is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

SLOTS, PAGE_SIZE, MAX_LEN, NEW_TOKENS = 8, 16, 1024, 32
# layers stacked under each weight in the program check; the last is read
STACK = 3
# the matmul weights of each served block program, by record name
SERVED_WEIGHTS = {"qkv": ("WQ", "WK", "WV"), "attn_out": ("WO",),
                  "mlp": ("Wg", "Wu", "Wd"), "mlp_up": ("Wg", "Wu"),
                  "mlp_down": ("Wd",)}
PROMPT_BUCKETS = ((97, 128), (400, 512))
# Largest error allowed for a compiled program, relative to the largest
# magnitude of the reference output.  The weights are stored in bf16 and
# promoted to f32 in the kernel, the other operands are f32; where the MXU
# evaluates an f32 product as bf16 passes, each product carries a relative
# error of at most 2**-8, which over the 128- to 9728-term sums here stays
# near 0.5% of the output's scale.  A wrong tile, index map or accumulation
# is off by the order of the output itself (>= 10%).
REL_TOL = 2e-2
# ``serve.*`` events that mean a request was retried or a compile was
# quarantined: a smoke run must have none
BAD_EVENTS = ("quarantine", "requeue", "device_step_failed",
              "retry_exhausted", "prep_failed", "prep_thread_restart")


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _rel_err(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference {ref.shape}")
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


# ------------------------------------------------------------ one chip
def _serve_round(api, engine, params, cfg, rng, uid0: int, problems):
    import numpy as np

    lens = [int(rng.integers(lo, hi + 1))
            for _ in range(SLOTS // len(PROMPT_BUCKETS))
            for lo, hi in PROMPT_BUCKETS]
    for i, n in enumerate(lens):
        engine.submit(api.Request(
            uid=uid0 + i, prompt=rng.integers(0, cfg.vocab, n, dtype=np.int32),
            sampling=api.SamplingParams(max_new_tokens=NEW_TOKENS)))
    t0 = time.perf_counter()
    done = engine.run(params, max_steps=4 * NEW_TOKENS)
    wall = time.perf_counter() - t0
    if len(done) != len(lens):
        problems.append(f"{len(done)} of {len(lens)} requests finished")
    for r in done:
        if r.status != "ok" or len(r.out_tokens) != NEW_TOKENS:
            problems.append(f"request {r.uid}: status {r.status!r}, "
                            f"{len(r.out_tokens)} tokens {r.error}")
        if not all(0 <= t < cfg.vocab for t in r.out_tokens):
            problems.append(f"request {r.uid}: token id out of the vocabulary")
    return sum(len(r.out_tokens) for r in done), wall, sorted(lens)


def _decode_references(progs, cfg, rng):
    """(name, program, inputs, reference outputs, rows compared) for the
    five decode programs, on seeded inputs at the served widths: each
    weight stacked ``(STACK, ...)`` in its declared (stored) dtype and
    handed with the index of its last slice, the paged keys and values as
    ``_paged_pool`` makes them, everything else f32.  The reference reads
    the same slice, or the gathered window with the rows past each
    slot's length at zero, cast to f32; scores are compared below each
    slot's length only (past it they are unspecified)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lower_jnp import Stacked
    from repro.serving.stripe_decode import WEIGHT_INPUTS

    hp = jax.lax.Precision.HIGHEST

    def ein(spec, a, b):
        a, b = (v.select() if isinstance(v, Stacked) else v for v in (a, b))  # Paged too
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=hp)

    def inputs(prog):
        out = {}
        for name in prog.program.inputs:
            decl = prog.program.buffers[name]
            if name in WEIGHT_INPUTS:
                a = rng.standard_normal((STACK, *decl.shape), dtype=np.float32)
                out[name] = Stacked(jnp.asarray(a, decl.dtype), STACK - 1)
                continue
            a = rng.standard_normal(decl.shape, dtype=np.float32)
            if name == "P":  # attention probabilities over the window
                a = np.asarray(jax.nn.softmax(jnp.asarray(a), axis=-1))
            out[name] = jnp.asarray(a)
        return out

    a = inputs(progs.qkv)
    yield "qkv", progs.qkv, a, {"Q": ein("bd,de->be", a["X"], a["WQ"]),
                                "K": ein("bd,de->be", a["X"], a["WK"]),
                                "V": ein("bd,de->be", a["X"], a["WV"])}, None
    a = inputs(progs.attn_out)
    yield "attn_out", progs.attn_out, a, {
        "Y": ein("be,ed->bd", a["A"], a["WO"]) + a["R"]}, None
    a = inputs(progs.mlp)
    h = jax.nn.silu(ein("bd,df->bf", a["X"], a["Wg"])) * ein("bd,df->bf", a["X"], a["Wu"])
    yield "mlp", progs.mlp, a, {"Y": ein("bf,fd->bd", h, a["Wd"]) + a["R"]}, None
    pool = _paged_pool(progs.paged_scores, rng)
    a = inputs(progs.paged_scores)
    a["K"] = pool
    valid = _live_rows(pool)
    yield "paged_scores", progs.paged_scores, a, {
        "S": jnp.where(valid, ein("bkgd,btkd->bkgt", a["Q"], pool), 0.0)}, valid
    a = inputs(progs.paged_values)
    a["V"] = pool
    a["P"] = jnp.where(_live_rows(pool), a["P"], 0.0)
    yield "paged_values", progs.paged_values, a, {
        "O": ein("bkgt,btkd->bkgd", a["P"], pool)}, None


def _paged_pool(prog, rng):
    """A ``Paged`` input of a paged program: ``STACK`` layers of a pool
    of twice the pages the slots can hold, each slot's pages drawn at
    random, the last layer read.  Slot lengths: 0, 1, one page, a whole
    window less one row, the rest at random; every row at or past a
    slot's length is NaN, as a recycled page may hold."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lower_jnp import Paged

    name = next(n for n in prog.program.inputs if prog.program.buffers[n].paged)
    decl = prog.program.buffers[name]
    m, window, row = decl.shape[0], decl.shape[1], decl.shape[2:]
    page = decl.paged
    pps = window // page
    n_pages = 2 * m * pps
    table = rng.permutation(n_pages)[: m * pps].reshape(m, pps).astype(np.int32)
    lengths = rng.integers(1, window, m).astype(np.int32)
    lengths[:4] = (0, 1, page, window - 1)[:m]
    pool = rng.standard_normal((STACK, n_pages, page) + row, dtype=np.float32)
    live = np.zeros((n_pages, page), bool)
    for s in range(m):
        for t in range(lengths[s]):
            live[table[s, t // page], t % page] = True
    pool[-1][~live] = np.nan
    return Paged(jnp.asarray(pool, decl.dtype), STACK - 1, table=jnp.asarray(table),
                 lengths=jnp.asarray(lengths))


def _live_rows(pool):
    """``(slots, 1, 1, window)``: the rows below each slot's length."""
    import jax.numpy as jnp

    window = pool.table.shape[1] * pool.array.shape[2]
    return (jnp.arange(window)[None, :] < pool.lengths[:, None])[:, None, None, :]


def one_chip(args, api, jax, on_tpu: bool, problems) -> None:
    import numpy as np

    cfg = api.configs.get("qwen3-4b")
    if args.tiny:
        cfg = cfg.scaled()
    print(f"config {cfg.name}{' (scaled)' if args.tiny else ''}: "
          f"{cfg.n_layers} layers, d_model {cfg.d_model}, {cfg.n_heads} query / "
          f"{cfg.n_kv_heads} KV heads x {cfg.hd}, d_ff {cfg.d_ff} ({cfg.act}), "
          f"vocab {cfg.vocab}, tied embeddings {cfg.tie_embeddings}, {cfg.dtype}")
    model = api.build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    n_bytes = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(params))
    print(f"init: {n_params} parameters, {n_bytes} bytes, "
          f"{time.perf_counter() - t0:.2f} s")

    # on the CPU the rehearsal asks for Pallas (interpret mode) explicitly;
    # on a TPU every option is the engine's platform default
    engine = api.ServingEngine(model, api.EngineConfig(
        slots=SLOTS, page_size=PAGE_SIZE, max_len=MAX_LEN,
        backend=None if on_tpu else "pallas"))
    ec = engine.config
    print(f"engine: backend {ec.backend}, hw {ec.hw}, interpret {ec.interpret}, "
          f"{ec.slots} slots, page_size {ec.page_size}, max_len {ec.max_len}")
    if on_tpu and (ec.backend != "pallas" or ec.interpret):
        problems.append("the engine does not default to compiled Pallas on a TPU")

    rng = np.random.default_rng(args.seed)
    tokens, wall, lens = _serve_round(api, engine, params, cfg, rng, 0, problems)
    print(f"cold round: prompt lengths {lens}, {tokens} tokens in {wall:.2f} s "
          "(includes compiles)")
    for e in engine.compile_log():
        where = f" bucket {e['bucket']}" if "bucket" in e else ""
        print(f"compile: {e['kind']}{where}: {e['first_call_s']:.2f} s")
        if e["kind"] == "prefill_fallback":
            problems.append(f"prefill bucket {e.get('bucket')} fell back to jnp")
    tokens, wall, lens = _serve_round(api, engine, params, cfg, rng, SLOTS, problems)
    print(f"warm round: prompt lengths {lens}, {tokens} tokens in {wall:.2f} s: "
          f"{tokens / wall:.1f} tokens/s (smoke figure, not a benchmark)")

    stats = jax.devices()[0].memory_stats() or {}
    print(f"device peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")

    narrow = np.dtype(cfg.dtype).itemsize < 4
    for name, rec in sorted(engine.compile_records().items()):
        print(f"record {name}: backend {rec.backend}, n_kernels {rec.n_kernels}, "
              f"block_backends {rec.block_backends}")
        print(f"  stored_reads {rec.stored_reads}")
        for w in SERVED_WEIGHTS.get(name.split("/")[-1], ()):
            read = rec.stored_reads.get(w, {})
            if not read.get("in_place") or read.get("narrow") != narrow:
                problems.append(f"record {name}: weight {w} not read as stored "
                                f"in place: {read}")
        for blk, why in rec.block_fallbacks.items():
            print(f"  legality fallback {blk}: {why}")
        if rec.quarantined or "compile crashed" in rec.fallback_reason:
            problems.append(f"record {name}: crash-class fallback: "
                            f"{rec.fallback_reason[:300]}")
    for ev in engine.events():
        if ev["event"] in BAD_EVENTS:
            problems.append(f"engine event {ev}")
    engine.close()
    from repro.obs import metrics as obs_metrics

    read, window = (obs_metrics.counter(f"serve.kv.{n}").value
                    for n in ("pages_read", "pages_window"))
    print(f"KV pages read: {read} of a full window's {window} "
          f"({read / max(window, 1):.3f})")
    if not 0 < read < window:
        problems.append(f"KV pages read {read} of {window}")
    rebuilds = obs_metrics.counter("serve.kv.pool_rebuilds").value
    print(f"KV pool rebuilds: {rebuilds}")
    if rebuilds:
        problems.append(f"KV pools rebuilt {rebuilds} times after failed steps")

    for name, prog, inputs, want, rows in _decode_references(
            engine.decode_programs(), cfg, np.random.default_rng(args.seed + 1)):
        got = jax.block_until_ready(prog(inputs))
        if rows is not None:
            got = {k: jax.numpy.where(rows, v, 0.0) for k, v in got.items()}
        err = max(_rel_err(got[k], v) for k, v in want.items())
        print(f"program {name}: max error {err:.3e} of the reference's scale "
              f"(tolerance {REL_TOL:g}), backend {prog.record.backend}")
        if not err <= REL_TOL:
            problems.append(f"program {name}: error {err:.3e} > {REL_TOL:g}")


# ---------------------------------------------------------- four chips
def _glu_ffn(api, m: int, d: int, f: int):
    """The served SiLU-GLU MLP block (``stripe_decode.build_mlp_program``)."""
    tp = api.TileProgram(f"mesh_glu_ffn_m{m}")
    tp.input("X", (m, d)); tp.input("R", (m, d)); tp.input("Wg", (d, f))
    tp.input("Wu", (d, f)); tp.input("Wd", (f, d))
    tp.temp("G", (m, f)); tp.temp("U", (m, f)); tp.temp("A", (m, f))
    tp.temp("O", (m, d)); tp.output("Y", (m, d))
    tp.op("G[b, f] += X[b, d] * Wg[d, f]", name="mm_gate")
    tp.op("U[b, f] += X[b, d] * Wu[d, f]", name="mm_up")
    tp.op("A[b, f] = silu(G[b, f]) * U[b, f]", name="glu")
    tp.op("O[b, d2] += A[b, f] * Wd[f, d2]", name="mm_down")
    tp.op("Y[b, d2] = O[b, d2] + R[b, d2]", name="resid")
    return tp.build()


def _kred(api, m: int, k: int, n: int):
    """A matmul whose only mesh-divisible index is the contraction."""
    tp = api.TileProgram(f"mesh_kred_k{k}")
    tp.input("X", (m, k)); tp.input("W", (k, n)); tp.output("O", (m, n))
    tp.op("O[i, j] += X[i, c] * W[c, j]", name="mm")
    return tp.build()


def four_chips(args, api, jax, on_tpu: bool, problems) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import mesh_lower, platform

    n = args.chips
    cfg = api.configs.get("qwen3-4b")
    d, f = (64, 256) if args.tiny else (cfg.d_model, cfg.d_ff)
    hw = api.get_config(platform.default_hw_name())
    hp = jax.lax.Precision.HIGHEST
    ein = lambda spec, a, b: jnp.einsum(spec, a, b, precision=hp)  # noqa: E731

    def glu_ref(a):
        h = jax.nn.silu(ein("bd,df->bf", a["X"], a["Wg"])) * ein("bd,df->bf", a["X"], a["Wu"])
        return ein("bf,fd->bd", h, a["Wd"]) + a["R"]

    cases = [
        ("glu_ffn", _glu_ffn(api, 128, d, f), "all_gather", "Y", glu_ref),
        ("kred", _kred(api, 6, f, 10), "psum", "O",
         lambda a: ein("ic,cj->ij", a["X"], a["W"])),
    ]
    rng = np.random.default_rng(args.seed)
    for name, prog, collective, out, ref_fn in cases:
        arrays = {b: jnp.asarray(rng.standard_normal(prog.buffers[b].shape,
                                                     dtype=np.float32))
                  for b in prog.inputs}
        t0 = time.perf_counter()
        meshed = api.jit(prog, hw, backend="pallas", mesh=n)
        got = jax.block_until_ready(meshed(arrays)[out])
        t_mesh = time.perf_counter() - t0
        single = api.jit(prog, hw, backend="pallas")
        one = jax.block_until_ready(single(arrays)[out])
        info = meshed.record.mesh
        print(f"mesh {name}: compile+run {t_mesh:.2f} s, mesh {info.get('shape')}, "
              f"collectives {[c['collective'] for c in info.get('collectives', ())]}, "
              f"backends {meshed.record.block_backends}")
        if "fallback" in info:
            problems.append(f"mesh {name}: planner fell back: {info['fallback']}")
            continue
        emitted = mesh_lower.count_collectives(meshed, arrays)
        planned = mesh_lower.expected_primitive_counts_from_record(info)
        print(f"mesh {name}: emitted collectives {emitted}, planned {planned}")
        if emitted != planned or collective not in emitted:
            problems.append(f"mesh {name}: emitted {emitted} != planned {planned} "
                            f"(expected a {collective})")
        devices = {dv.id for dv in got.sharding.device_set}
        if len(devices) != n:
            problems.append(f"mesh {name}: output on devices {sorted(devices)}")
        if meshed.record.backend != "pallas" or single.record.backend != "pallas":
            problems.append(f"mesh {name}: not lowered to Pallas "
                            f"({meshed.record.fallback_reason[:200]})")
        e_one = _rel_err(got, one)
        e_ref = _rel_err(got, ref_fn(arrays))
        print(f"mesh {name}: error vs single device {e_one:.3e}, vs jnp HIGHEST "
              f"{e_ref:.3e} (tolerance {REL_TOL:g}), devices {sorted(devices)}")
        if not max(e_one, e_ref) <= REL_TOL:
            problems.append(f"mesh {name}: error {max(e_one, e_ref):.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="scaled config, to rehearse on the CPU (still exits 1)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-device compile path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no repro package at {SRC}: run from a checkout of the repository")
    sys.path.insert(0, SRC)
    if args.tiny and args.chips > 1 and "device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={args.chips}")

    import jax

    from repro import api

    api.enable_compilation_cache(HERE)
    devs = jax.devices()
    dev = devs[0]
    on_tpu = dev.platform == "tpu"
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, count {len(devs)}")
    if not on_tpu and not args.tiny:
        _fail(f"no TPU: JAX found {dev.platform} devices only")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices; found {len(devs)}")

    problems = []
    (one_chip if args.chips == 1 else four_chips)(args, api, jax, on_tpu, problems)
    for p in problems:
        print(f"chip_smoke: {p}", file=sys.stderr)
    if problems:
        _fail(f"{len(problems)} check(s) failed")
    if not on_tpu:
        _fail("rehearsal passed, but there is no TPU: no result")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
