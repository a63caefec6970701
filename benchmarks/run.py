"""Benchmark harness — one function per paper table/figure plus framework
benches.  Prints ``name,us_per_call,derived`` CSV rows; ``--json OUT.json``
additionally writes the same records as machine-readable JSON
(``[{name, us_per_call, derived}, ...]``) so CI can archive perf
trajectories; ``--only fig4,fig5`` selects a subset.

Paper artifacts (Stripe has no numeric tables; its quantitative artifacts
are the Fig. 1 engineering-effort comparison and the Fig. 4/5 autotiling
example, both reproduced exactly):

* fig1: engineering-effort counts (kernel-library vs schedule-space vs
  Stripe) computed from this repo's actual artifact counts.
* fig4: the cache-line cost model on the 3x3 conv — cost of the Fig.5b
  tiling (54 lines / tile pair) and the autotiler's pick.
* fig5: the tiling rewrite — wall-clock of the XLA-compiled lowering
  before/after the pass pipeline (semantics asserted equal).

Framework benches: the api.stripe_jit compile cache (cold vs warm-memory vs
warm-disk), whole-program fusion groups, the liveness-based VMEM memory
planner (arena before/after reuse + the capacity-unlock speedup),
Stripe-matmul kernel vs plain einsum (CPU wall time), per-arch reduced
train step, flash-attention block-size choice, and the design-space
exploration smoke sweep.
"""
import argparse
import json
import os
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS: List[Dict[str, Any]] = []
# --profile: compile with stripe_jit(..., profile=True) in the cache and
# serving benches (measured per-unit latencies + cost-model residual rows)
PROFILE = False


def emit(name: str, us_per_call: float, derived: Any) -> None:
    print(f"{name},{us_per_call:.2f},{derived}")
    RESULTS.append({"name": name, "us_per_call": round(float(us_per_call), 2),
                    "derived": derived})


def _timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def bench_fig1_engineering_effort() -> None:
    """Fig 1: artifacts needed per approach for our 10 archs x 3 hw
    configs x K ops.  Stripe: ops + hw-configs; kernel library:
    ops x hw x versions."""

    n_ops = 4          # matmul, attention-score, gla-chunk, conv (frontend ops)
    n_hw = len(api.HW_REGISTRY)
    n_arch = len(api.configs.names())
    kernel_lib = n_ops * n_hw * n_arch          # per-op-per-hw-per-shape family
    schedule_space = n_ops * n_hw + n_ops       # spaces + algorithms
    stripe = n_ops + n_hw                       # algorithms + configs
    emit("fig1_artifacts_kernel_library", 0.0, kernel_lib)
    emit("fig1_artifacts_schedule_space", 0.0, schedule_space)
    emit("fig1_artifacts_stripe", 0.0, stripe)


def bench_fig4_autotile() -> None:

    prog = api.single_op_program(
        "O[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]",
        {"I": ((12, 16, 8), "int8"), "F": ((3, 3, 8, 16), "int8"),
         "O": ((12, 16, 16), "int32")},
        out="O",
    )
    blk = prog.entry.stmts[0]
    hw = api.get_config("paper_fig4")
    params = dict(hw.passes[0][1])
    ref = api.evaluate_tiling(blk, {"x": 3, "y": 4}, hw, params)
    t0 = time.perf_counter()
    tiles, best = api.choose_tiling(blk, hw, params)
    dt = (time.perf_counter() - t0) * 1e6
    emit("fig4_cost_fig5b_tiling", 0.0, f"{ref.cost:.6f}")
    emit("fig4_lines_per_tilepair", 0.0, f"{ref.lines / ref.n_tiles:.0f}")
    emit("fig4_autotile_best_cost", dt, f"{best.cost:.6f}")
    emit("fig4_autotile_tiles", 0.0, f"\"{tiles}\"")


def bench_fig5_rewrite() -> None:
    """Tiling-rewrite overhead + executable equivalence (reduced shape)."""
    import copy


    prog = api.single_op_program(
        "O[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]",
        {"I": ((12, 16, 8), "float32"), "F": ((3, 3, 8, 16), "float32"),
         "O": ((12, 16, 16), "float32")},
        out="O",
    )
    src = copy.deepcopy(prog)
    t0 = time.perf_counter()
    opt = api.compile_program(prog, api.get_config("cpu_test"))
    dt_compile = (time.perf_counter() - t0) * 1e6
    rng = np.random.RandomState(0)
    arrays = {"I": rng.randn(12, 16, 8).astype(np.float32),
              "F": rng.randn(3, 3, 8, 16).astype(np.float32)}
    a = api.execute_reference(src, arrays)["O"]
    b = api.execute_reference(opt, arrays)["O"]
    equal = bool(np.allclose(a, b, rtol=1e-4, atol=1e-5))
    fn = jax.jit(lambda d: api.lower_program_jnp(opt.source)(d)["O"])
    dt_exec = _timeit(fn, {k: jnp.asarray(v) for k, v in arrays.items()})
    emit("fig5_pass_pipeline_compile", dt_compile, 1)
    emit("fig5_semantics_preserved", 0.0, int(equal))
    emit("fig5_conv_exec_jnp", dt_exec, 1)


def bench_stripe_jit_cache() -> None:
    """Tentpole metric: warm vs cold ``api.stripe_jit`` compile of the Fig. 5
    conv — in-memory hit and cross-process (disk tiling replay) warm."""
    import tempfile


    def conv():
        return api.single_op_program(
            "O[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]",
            {"I": ((12, 16, 8), "float32"), "F": ((3, 3, 8, 16), "float32"),
             "O": ((12, 16, 16), "float32")},
            out="O",
        )

    with tempfile.TemporaryDirectory() as d:
        cache = api.CompilationCache(disk_dir=d)
        t0 = time.perf_counter()
        api.stripe_jit(conv(), api.get_config("cpu_test"), cache=cache)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        api.stripe_jit(conv(), api.get_config("cpu_test"), cache=cache)
        warm_mem = time.perf_counter() - t0
        # fresh cache instance over the same disk dir = a new process
        cache2 = api.CompilationCache(disk_dir=d)
        t0 = time.perf_counter()
        cp = api.stripe_jit(conv(), api.get_config("cpu_test"), cache=cache2)
        warm_disk = time.perf_counter() - t0
        assert cp.record.disk_hit
    emit("stripe_jit_compile_cold", cold * 1e6, 1)
    emit("stripe_jit_compile_warm_mem", warm_mem * 1e6, f"{cold / warm_mem:.0f}x")
    emit("stripe_jit_compile_warm_disk", warm_disk * 1e6, f"{cold / warm_disk:.1f}x")

    if PROFILE:
        # profiled compile: first dispatch wall-times each lowered unit and
        # appends (predicted, measured) rows to the residual log
        with tempfile.TemporaryDirectory() as d:
            cache = api.CompilationCache(disk_dir=d)
            cp = api.stripe_jit(conv(), api.get_config("cpu_test"),
                                cache=cache, profile=True)
            rng = np.random.RandomState(0)
            cp({"I": rng.randn(12, 16, 8).astype(np.float32),
                "F": rng.randn(3, 3, 8, 16).astype(np.float32)})
            rows = obs.read_residuals(obs.residual_log_path(cache))
            emit("stripe_jit_profiled_units", 0.0,
                 len(cp.record.measured_latency_s))
            emit("stripe_jit_residual_rows", 0.0, len(rows))


def _fusion_chain_prog(act_ops):
    """matmul -> bias -> <act chain> -> matmul on wide activations with a
    skinny contraction dim, so intermediate-tensor traffic (what fusion
    eliminates) dominates compute."""

    m, k, n, n2 = 1024, 8, 4096, 8
    tp = api.TileProgram("fusion_bench")
    tp.input("A", (m, k))
    tp.input("B", (k, n))
    tp.input("b", (n,))
    tp.input("W2", (n, n2))
    tp.temp("T", (m, n))
    tp.temp("U0", (m, n))
    tp.output("O", (m, n2))
    tp.op("T[i, j] += A[i, c] * B[c, j]", name="mm1")
    tp.op("U0[i, j] = T[i, j] + b[j]", name="bias")
    cur = "U0"
    for idx, opname in enumerate(act_ops):
        nxt = f"U{idx + 1}"
        tp.temp(nxt, (m, n))
        tp.op(f"{nxt}[i, j] = {opname}({cur}[i, j])", name=f"act{idx}")
        cur = nxt
    tp.op(f"O[i, j2] += {cur}[i, j] * W2[j, j2]", name="mm2")
    return tp.build()


def _fusion_measure(prog):
    """(t_unfused, t_fused, n_unfused, n_fused): interleaved rounds with
    min-of-rounds per path — scheduling contention on shared hosts only
    ever *adds* time, so the per-path minimum is the noise-robust
    estimator (timeit's rationale), and interleaving spreads contention
    bursts across both paths."""
    import copy


    semantic = copy.deepcopy(prog)
    # CPU parameterization: prologue-preferred grouping ends each group's
    # executable with its contraction, keeping XLA:CPU's gemm on its
    # library path (the default epilogue grouping is the right shape for
    # the Pallas/TPU backend, which applies epilogues on the accumulator
    # tile).
    hw_cpu = api.get_config("tpu_v5e").with_params(**{"fuse.prefer": "prologue"})
    compiled = api.stripe_jit(copy.deepcopy(prog), hw_cpu, backend="jnp")
    unfused_fn = api.lower_program_jnp(semantic, groups=None, jit_scope="op")
    fused_fn = api.lower_program_jnp(semantic, groups=compiled.record.groups,
                                 jit_scope="group")
    rng = np.random.RandomState(0)
    arrays = {nm: jnp.asarray(rng.randn(*semantic.buffers[nm].shape), jnp.float32)
              for nm in semantic.inputs}
    a = unfused_fn(arrays)["O"]
    c = fused_fn(arrays)["O"]
    assert np.allclose(np.asarray(a), np.asarray(c), rtol=1e-4, atol=1e-4)
    for _ in range(2):
        _timeit(unfused_fn, arrays, n=2, warmup=1)
        _timeit(fused_fn, arrays, n=2, warmup=1)
    t_u, t_f = [], []
    for r in range(12):
        pair = [(_timeit(unfused_fn, arrays, n=3, warmup=0), t_u),
                (_timeit(fused_fn, arrays, n=3, warmup=0), t_f)]
        if r % 2:
            pair.reverse()
        for t, acc in pair:
            acc.append(t)
    return min(t_u), min(t_f), unfused_fn.n_kernels, fused_fn.n_kernels


def bench_fusion() -> None:
    """Whole-program fusion groups: fused (per-group lowering — one
    dispatch/kernel per fusion group, group-internal intermediates never
    materialized) vs unfused (per-op lowering — one dispatch per op,
    every intermediate round-tripping through memory).

    Two chains are measured.  The canonical matmul->bias->gelu->matmul
    chain reports kernels launched + µs/call, but its CPU wall time is
    dominated by XLA:CPU's erf codegen, whose vectorization is
    nondeterministic *per compilation* — the measured ratio swings with
    that coin flip, not with fusion.  The headline ``fusion_speedup``
    therefore comes from the transcendental-free relu² variant
    (nemotron-style squared-ReLU FFN, also exercised by this repo's
    configs), where the eliminated intermediate traffic is the whole
    story and the measurement is stable.  The Pallas lowering of the
    gelu chain is also compiled to record kernels-per-chain (4 ops -> 2
    fusion groups -> 2 pallas_calls)."""
    import copy


    gelu_prog = _fusion_chain_prog(["gelu"])
    semantic = copy.deepcopy(gelu_prog)
    t_u, t_f, n_u, n_f = _fusion_measure(gelu_prog)
    emit("fusion_unfused_per_op", t_u, n_u)
    emit("fusion_fused_groups", t_f, n_f)
    emit("fusion_gelu_speedup", 0.0, f"{t_u / t_f:.2f}x")

    relu2_prog = _fusion_chain_prog(["relu", "square"])
    t_u2, t_f2, n_u2, n_f2 = _fusion_measure(relu2_prog)
    emit("fusion_relu2_unfused_per_op", t_u2, n_u2)
    emit("fusion_relu2_fused_groups", t_f2, n_f2)
    emit("fusion_speedup", 0.0, f"{t_u2 / t_f2:.2f}x")

    pallas = api.stripe_jit(semantic, api.get_config("tpu_v5e"), backend="pallas")
    emit("fusion_pallas_kernels", 0.0,
         f"\"{n_u}->{pallas.record.n_kernels} "
         f"(backend={pallas.record.backend})\"")


def bench_memplan() -> None:
    """Liveness-based VMEM memory planner (core/memplan.py).

    Part 1 — arena before/after reuse: compile the explore ``default``
    corpus on stock tpu_v5e and report, per workload, the planner's peak
    arena vs the legacy bump model (no liveness, every view blanket-
    double-buffered) from the ``arena:``/``arena_bump:`` tags of the
    same compile.

    Part 2 — capacity unlock: a relu->square->abs chain feeding a
    skinny matmul with a reduction-resident weight, on a VMEM-tight
    config whose capacity sits *between* the legacy ``2x`` pressure and
    the planner's exact footprint.  The legacy model both rejects the
    chain inline (4 kernels, 3 materialized intermediates) and caps the
    matmul at a smaller tile; the planner fuses the whole chain into
    one kernel and picks a larger tile that the ``2x`` rule called
    infeasible.  Measured jnp latency (per-group lowering, min-of-
    rounds) quantifies the unlock."""
    import copy


    # ---- part 1: default-corpus arena peaks (planner vs bump) -------------
    # read from the schedule pass's report: the planner's per-block arena
    # vs the legacy bump model priced on the same views (NOT the score's
    # vmem_peak_bytes, which also floors at the autotile tile footprint)
    hw0 = api.get_config("tpu_v5e")
    workloads = api.get_workloads("default")
    lower = 0
    for w in workloads:
        _, rec = api.compile_cached(w.build(), hw0, use_disk=False)
        sched = [r for e in rec.pass_trace if e[0] == "schedule"
                 for r in e[2] if isinstance(r, dict)]
        planner_peak = max((r.get("arena_bytes", 0) for r in sched), default=0)
        bump_peak = max((r.get("arena_bump_bytes", 0) for r in sched), default=0)
        if 0 < planner_peak < bump_peak:
            lower += 1
        emit(f"memplan_arena/{w.name}", 0.0, f"\"{planner_peak}/{bump_peak}B\"")
    emit("memplan_arena_workloads_lower", 0.0, f"{lower}/{len(workloads)}")

    # ---- part 2: capacity unlock on a VMEM-tight config -------------------
    m, n, n2 = 1024, 4096, 32

    def chain():
        tp = api.TileProgram("memplan_chain")
        tp.input("X", (m, n))
        tp.input("W2", (n, n2))
        tp.temp("Y1", (m, n))
        tp.temp("Y2", (m, n))
        tp.temp("X2", (m, n))
        tp.output("O", (m, n2))
        tp.op("Y1[i, j] = relu(X[i, j])", name="pre1")
        tp.op("Y2[i, j] = square(Y1[i, j])", name="pre2")
        tp.op("X2[i, j] = abs(Y2[i, j])", name="pre3")
        tp.op("O[i, j2] += X2[i, j] * W2[j, j2]", name="mm")
        return tp.build()

    # cap = 0.29 * 16 MiB = 4.87 MB sits between the planner's exact
    # pressure of the chain-inline trial (~4.6 MB: W2 resident, one
    # accumulator slot) and the legacy 2x rule (~5.06 MB)
    hw = (api.get_config("tpu_v5e").with_mem("VMEM", size_bytes=16 * 2**20)
          .with_params(**{"autotile.mem_cap_frac": 0.29,
                          "fuse.mem_cap_frac": 0.29}))
    legacy = hw.with_params(**{"fuse.memplan": False, "autotile.memplan": False,
                               "schedule.memplan": False})
    recs = {}
    for name, cfg in (("planner", hw), ("legacy", legacy)):
        c = api.stripe_jit(chain(), cfg, backend="jnp", use_disk=False)
        recs[name] = c.record
    assert recs["planner"].n_kernels == 1 and recs["legacy"].n_kernels == 4

    def mm_rec(rec):
        for e in rec.pass_trace:
            if e[0] == "autotile":
                for r in e[2]:
                    if r["block"] == "mm":
                        return r
        raise AssertionError("no autotile record for mm")

    mm_p, mm_l = mm_rec(recs["planner"]), mm_rec(recs["legacy"])
    cap = int(16 * 2**20 * 0.29)
    # the planner's (larger) tile was infeasible under the legacy 2x rule
    assert mm_p["mem_bytes"] > mm_l["mem_bytes"]
    assert 2 * mm_p["mem_bytes"] > cap >= mm_p["plan_bytes"]
    lat_p = api.score_pass_trace(recs["planner"].pass_trace).latency_s
    lat_l = api.score_pass_trace(recs["legacy"].pass_trace).latency_s
    emit("memplan_tiles_planner", 0.0, f"\"{mm_p['tiles']} ({mm_p['mem_bytes']}B)\"")
    emit("memplan_tiles_legacy", 0.0, f"\"{mm_l['tiles']} ({mm_l['mem_bytes']}B)\"")
    emit("memplan_pred_speedup", 0.0, f"{lat_l / lat_p:.2f}x")

    prog = chain()
    rng = np.random.RandomState(0)
    arrays = {"X": jnp.asarray(rng.randn(m, n), jnp.float32),
              "W2": jnp.asarray(rng.randn(n, n2), jnp.float32)}
    fn_p = api.lower_program_jnp(copy.deepcopy(prog), groups=recs["planner"].groups,
                             jit_scope="group")
    fn_l = api.lower_program_jnp(copy.deepcopy(prog), groups=recs["legacy"].groups,
                             jit_scope="group")
    a = np.asarray(fn_p(arrays)["O"])
    b = np.asarray(fn_l(arrays)["O"])
    assert np.allclose(a, b, rtol=1e-4, atol=1e-4)
    for _ in range(2):
        _timeit(fn_l, arrays, n=2, warmup=1)
        _timeit(fn_p, arrays, n=2, warmup=1)
    t_l, t_p = [], []
    for r in range(8):
        pair = [(_timeit(fn_l, arrays, n=3, warmup=0), t_l),
                (_timeit(fn_p, arrays, n=3, warmup=0), t_p)]
        if r % 2:
            pair.reverse()
        for t, acc in pair:
            acc.append(t)
    emit("memplan_measured_legacy", min(t_l), recs["legacy"].n_kernels)
    emit("memplan_measured_planner", min(t_p), recs["planner"].n_kernels)
    emit("memplan_measured_speedup", 0.0, f"{min(t_l) / min(t_p):.2f}x")


def bench_conv() -> None:
    """Halo-aware conv lowering + per-block hybrid backend.

    * fig4/fig5: the paper's conv now compiles to real ``pallas_call``
      kernels (previously any halo view forced a whole-program jnp
      fallback); interpret-mode output is asserted equal to the reference
      interpreter — bit-exact for the int8 fig4 program.  This is the CI
      path that runs fig4/fig5 through pallas-interpret.
    * measured: the kernelized conv (pallas-interpret under jit) vs the
      jnp fallback path it replaces, at a serving-ish shape,
      min-of-interleaved-rounds.  Interpret mode emulates the kernel with
      jax ops on CPU, so the wall-clock ratio reflects only the
      structural savings (shifted-slice dots, masks confined to
      constraint-carrying pieces) — the VMEM-locality/MXU win needs
      hardware; the ratio is tracked to catch structural regressions.
    * hybrid: a mixed program (conv + channel-mix matmul + an
      unsupported max-aggregation head) keeps its conv and matmul
      kernels; only the max block falls back, per
      ``CompileRecord.block_backends``."""
    import copy


    hw = api.get_config("tpu_v5e")
    rng = np.random.RandomState(0)

    # ---- fig4/fig5 through pallas-interpret, asserted vs the reference ----
    for build, name in ((api.explore.workloads.fig4_conv, "fig4"),
                        (api.explore.workloads.fig5_conv_f32, "fig5")):
        prog = build()
        src = copy.deepcopy(prog)
        c = api.stripe_jit(prog, hw, backend="pallas", use_disk=False)
        assert c.record.backend == "pallas", c.record.fallback_reasons()
        assert c.record.n_kernels >= 1
        ins = {}
        for n in src.inputs:
            d = src.buffers[n]
            ins[n] = (rng.randint(-4, 5, d.shape).astype(np.int8)
                      if d.dtype == "int8"
                      else rng.randn(*d.shape).astype(np.float32))
        got = np.asarray(c(ins)["O"])
        want = api.execute_reference(src, ins)["O"]
        if want.dtype.kind in "iu":
            assert (got == want).all(), "int8 conv must be bit-exact"
        else:
            assert np.allclose(got, want, rtol=1e-4, atol=1e-4)
        emit(f"conv_{name}_pallas_kernels", 0.0,
             f"\"{c.record.n_kernels} (backend={c.record.backend})\"")

    # ---- measured: kernelized conv vs the jnp fallback it replaces --------
    x, y, ci, co = 96, 96, 16, 16
    prog = api.single_op_program(
        "O[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]",
        {"I": ((x, y, ci), "float32"), "F": ((3, 3, ci, co), "float32"),
         "O": ((x, y, co), "float32")}, out="O", name="conv_serving")
    pal = api.stripe_jit(copy.deepcopy(prog), hw, backend="pallas", use_disk=False)
    assert pal.record.backend == "pallas", pal.record.fallback_reasons()
    ref = api.stripe_jit(copy.deepcopy(prog), hw, backend="jnp", use_disk=False)
    ins = {"I": jnp.asarray(rng.randn(x, y, ci), jnp.float32),
           "F": jnp.asarray(rng.randn(3, 3, ci, co), jnp.float32)}
    pf = jax.jit(lambda a: pal(a)["O"])
    jf = jax.jit(lambda a: ref(a)["O"])
    assert np.allclose(np.asarray(pf(ins)), np.asarray(jf(ins)),
                       rtol=1e-3, atol=1e-3)
    for _ in range(2):
        _timeit(pf, ins, n=2, warmup=1)
        _timeit(jf, ins, n=2, warmup=1)
    t_p, t_j = [], []
    for r in range(10):
        pair = [(_timeit(pf, ins, n=3, warmup=0), t_p),
                (_timeit(jf, ins, n=3, warmup=0), t_j)]
        if r % 2:
            pair.reverse()
        for t, acc in pair:
            acc.append(t)
    emit("conv_exec_pallas_interpret", min(t_p), pal.record.n_kernels)
    emit("conv_exec_jnp_fallback", min(t_j), ref.record.n_kernels)
    emit("conv_measured_speedup", 0.0, f"{min(t_j) / min(t_p):.2f}x")

    # ---- hybrid: mixed program keeps its kernels --------------------------
    tp = api.TileProgram("conv_mixed")
    tp.input("I", (24, 24, 8))
    tp.input("F", (3, 3, 8, 16))
    tp.input("W", (16, 32))
    tp.temp("C", (24, 24, 16))
    tp.output("O", (24, 24, 32))
    tp.output("M", (24, 24))
    tp.op("C[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]", name="conv")
    tp.op("O[x, y, m] += C[x, y, k] * W[k, m]", name="proj")
    tp.op("M[x, y] max= C[x, y, k]", name="headmax")  # no Pallas path
    mixed = tp.build()
    src = copy.deepcopy(mixed)
    hy = api.stripe_jit(mixed, hw, backend="pallas", use_disk=False)
    rec = hy.record
    assert rec.backend == "pallas"
    assert rec.block_backends.get("headmax") == "jnp"
    assert all(b == "pallas" for u, b in rec.block_backends.items()
               if u != "headmax"), rec.block_backends
    ins = {"I": rng.randn(24, 24, 8).astype(np.float32),
           "F": rng.randn(3, 3, 8, 16).astype(np.float32),
           "W": rng.randn(16, 32).astype(np.float32)}
    got = hy(ins)
    want = api.execute_reference(src, ins)
    for out in ("O", "M"):
        assert np.allclose(np.asarray(got[out]), want[out], rtol=1e-3, atol=1e-3)
    n_jnp = sum(1 for b in rec.block_backends.values() if b == "jnp")
    emit("conv_hybrid_kernels", 0.0,
         f"\"pallas={rec.n_kernels - n_jnp} jnp={n_jnp} "
         f"({' '.join(f'{u}={b}' for u, b in sorted(rec.block_backends.items()))})\"")


def bench_stripe_matmul() -> None:

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 512), jnp.float32)
    w = jnp.asarray(rng.randn(512, 384), jnp.float32)
    t_ref = _timeit(jax.jit(lambda a, b: api.matmul_ref(a, b)), x, w)
    got = api.matmul(x, w)
    err = float(jnp.max(jnp.abs(got - api.matmul_ref(x, w))))
    emit("stripe_matmul_ref_xla", t_ref, 1)
    emit("stripe_matmul_pallas_interpret_maxerr", 0.0, f"{err:.2e}")


def bench_flash_attention_blocks() -> None:
    import tempfile


    # isolate from ~/.cache/stripe-repro so the "cold" rows are really cold
    with tempfile.TemporaryDirectory() as d:
        api.set_default_cache(api.CompilationCache(disk_dir=d))
        try:
            for s in (4096, 32768):
                t0 = time.perf_counter()
                bq, bk = api.choose_block_sizes(s, s, 128)
                dt = (time.perf_counter() - t0) * 1e6
                emit(f"flash_attn_autotile_s{s}", dt, f"\"bq={bq} bk={bk}\"")
                # second call: served from the compilation cache
                t0 = time.perf_counter()
                api.choose_block_sizes(s, s, 128)
                dt_warm = (time.perf_counter() - t0) * 1e6
                emit(f"flash_attn_autotile_s{s}_cached", dt_warm, f"\"bq={bq} bk={bk}\"")
        finally:
            api.set_default_cache(None)


def bench_arch_steps() -> None:

    for name in api.configs.names():
        cfg = api.configs.get(name).scaled()
        m = api.build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = api.make_batch(cfg, "train", 2, 32)
        fn = jax.jit(lambda p, b: m.loss(p, b, remat=False)[0])
        dt = _timeit(fn, params, batch, n=3, warmup=1)
        emit(f"arch_train_step_reduced/{name}", dt, 1)


def bench_hillclimb() -> None:
    # the narrative lives in the explore subsystem now (one search impl)

    api.roofline_hillclimb(emit=emit)


def bench_explore() -> None:
    """Design-space exploration smoke: a tiny cost-model-only grid (<= 8
    points) over the TPU sweep on the quick corpus — asserts the sweep
    completes, dedupes the stock point against the baseline, and that at
    least one swept config beats stock predicted latency somewhere."""
    import tempfile


    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        sweep = api.run_sweep(api.get_space("tpu-sweep"), "quick", budget=8,
                          strategy="grid", cache_dir=d, measure_top_k=0)
        dt = (time.perf_counter() - t0) * 1e6
    n_dedup = sum(1 for p in sweep.points if p.dedup_of is not None)
    n_dominating = sum(1 for v in api.dominating_baseline(sweep).values() if v)
    emit("explore_sweep_8pt", dt, f"\"points={len(sweep.points)} dedup={n_dedup}\"")
    emit("explore_pareto_size", 0.0, len(api.pareto_front(sweep.points)))
    emit("explore_workloads_dominating_baseline", 0.0, n_dominating)
    best = min(sweep.unique_points(), key=lambda p: p.latency_s)
    emit("explore_best_vs_baseline_predicted", 0.0,
         f"{sweep.baseline.latency_s / max(best.latency_s, 1e-30):.2f}x")


def bench_serving() -> None:
    """Serving smoke: ~100 synthetic requests (Poisson arrival stamps,
    mixed prompt lengths) through the continuous-batching engine vs the
    wave baseline at equal slot count, on a reduced dense LM.

    Two legs:

    * **parity** — uniform prompt length (the wave engine left-pads
      without masking, so mixed lengths are not numerically comparable),
      asserting *identical output tokens* from both engines;
    * **traffic** — 100 mixed-length requests queued per Poisson arrival
      order, reporting tokens/s, p50/p99 request completion latency and
      slot utilization for each engine.  Both engines are warmed on a
      throwaway request set first so the leg measures steady-state
      serving, not jit/stripe compile time (cold-boot cost is the
      compile-cache warm-start story, reported by ``compile_log()``).
    """
    cfg = api.configs.get("llama3-8b").scaled(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab=97, dtype="float32")
    model = api.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    slots, max_len = 4, 64
    rng = np.random.RandomState(0)

    # ---- parity leg: identical tokens, wave vs continuous -----------------
    prompts = [rng.randint(1, cfg.vocab, size=6).astype(np.int32)
               for _ in range(2 * slots)]
    cont = api.ServingEngine(
        model, api.EngineConfig(slots=slots, max_len=max_len, page_size=8))
    wave = api.WaveEngine(model, slots, max_len)
    for i, p in enumerate(prompts):
        for eng in (cont, wave):
            eng.submit(api.Request(uid=i, prompt=p.copy(),
                                   sampling=api.SamplingParams(max_new_tokens=8)))
    got_c = {r.uid: r.out_tokens for r in cont.run(params, max_steps=10_000)}
    got_w = {r.uid: r.out_tokens for r in wave.run(params, max_steps=10_000)}
    assert got_c == got_w, "continuous engine diverged from the wave baseline"
    rec = cont.compile_records()["decode/mlp"]
    emit("serving_parity_requests", 0.0, len(got_c))
    emit("serving_decode_stripe_kernels", 0.0,
         f"\"mlp={rec.n_kernels} groups={len(rec.groups)}\"")

    # ---- traffic leg: 100 mixed-length requests, Poisson arrivals ---------
    n_req = 100

    def mixed_requests(seed=7, base_uid=0):
        r = np.random.RandomState(seed)
        arrivals = np.cumsum(r.exponential(1.0, size=n_req))  # Poisson process
        reqs = []
        for i in range(n_req):
            plen = int(r.choice([4, 8, 16, 24]))
            new = int(r.randint(4, 17))
            reqs.append((arrivals[i], api.Request(
                uid=base_uid + i,
                prompt=r.randint(1, cfg.vocab, size=plen).astype(np.int32),
                sampling=api.SamplingParams(max_new_tokens=new))))
        return reqs

    for label, eng in (
            ("continuous", api.ServingEngine(
                model, api.EngineConfig(slots=slots, max_len=max_len,
                                        page_size=8, profile=PROFILE))),
            ("wave", api.WaveEngine(model, slots, max_len))):
        # warm-up pass (compiles every bucket), then the timed run
        for _, r in mixed_requests(seed=1, base_uid=10_000):
            eng.submit(r)
        eng.run(params, max_steps=100_000)
        reqs = mixed_requests()
        t0 = time.perf_counter()
        for _, r in reqs:  # arrival order; all queued (closed-loop smoke)
            eng.submit(r)
        done = eng.run(params, max_steps=100_000)
        wall = time.perf_counter() - t0
        assert len(done) == n_req, f"{label}: {len(done)}/{n_req} finished"
        toks = sum(len(r.out_tokens) for r in done)
        lats = np.sort([r.finish_time - t0 for r in done])
        p50, p99 = lats[int(0.50 * n_req)], lats[int(0.99 * n_req)]
        util = (eng.metrics()["slot_utilization"]
                if isinstance(eng, api.ServingEngine) else float("nan"))
        emit(f"serving_{label}_tok_per_s", wall / max(toks, 1) * 1e6,
             f"\"{toks / wall:.0f} tok/s p50={p50:.2f}s p99={p99:.2f}s "
             f"util={util:.2f}\"")

    # ---- tracing-overhead leg: traced vs untraced continuous serving ------
    # same warm engine, interleaved alternating-order rounds; the estimate
    # is the ratio of per-mode MEDIAN throughput — per-round scheduling
    # noise on a 2-core CI host is comparable to the effect being measured,
    # so extreme rounds in either direction must not decide the assertion.
    # Runs are 3x the traffic leg so each wall averages scheduler jitter,
    # and noisy hosts get extra rounds before the <= 5% assertion fires.
    import statistics

    from repro.obs import trace as obs_trace

    n_ov = 3 * n_req
    rng_ov = np.random.RandomState(11)
    plens = rng_ov.choice([4, 8, 16, 24], size=n_ov)
    news = rng_ov.randint(4, 17, size=n_ov)

    def overhead_requests(base_uid):
        r = np.random.RandomState(11)
        return [api.Request(
            uid=base_uid + i,
            prompt=r.randint(1, cfg.vocab, size=int(plens[i])).astype(np.int32),
            sampling=api.SamplingParams(max_new_tokens=int(news[i])))
            for i in range(n_ov)]

    eng = api.ServingEngine(
        model, api.EngineConfig(slots=slots, max_len=max_len, page_size=8,
                                profile=PROFILE))
    for _, r in mixed_requests(seed=1, base_uid=20_000):
        eng.submit(r)
    eng.run(params, max_steps=100_000)
    saved = obs_trace.get_tracer()
    tput = {False: [], True: []}
    uid, rounds, ratio = 30_000, 0, 0.0
    try:
        while True:
            order = (False, True) if rounds % 2 == 0 else (True, False)
            for traced in order:
                obs_trace.set_tracer(obs_trace.Tracer(enabled=traced))
                reqs = overhead_requests(uid)
                uid += n_ov
                t0 = time.perf_counter()
                for r in reqs:
                    eng.submit(r)
                done = eng.run(params, max_steps=100_000)
                wall = time.perf_counter() - t0
                assert len(done) == n_ov
                toks = sum(len(r.out_tokens) for r in done)
                tput[traced].append(toks / wall)
            rounds += 1
            ratio = (statistics.median(tput[True])
                     / statistics.median(tput[False]))
            if rounds >= 10 or (rounds >= 3 and ratio >= 0.95):
                break
    finally:
        obs_trace.set_tracer(saved)
    emit("serving_tracing_overhead", 0.0, f"\"{ratio:.3f}x ({rounds} rounds)\"")
    assert ratio >= 0.95, (
        f"traced serving throughput is {ratio:.3f}x untraced (< 0.95x) "
        f"after {rounds} interleaved rounds")


def bench_chaos() -> None:
    """Chaos smoke: the ``serve_traffic.py --faults`` leg at reduced
    scale.  Replays one Poisson trace through a clean and a faulted
    continuous engine (fault classes: prefill-compile crash, torn
    disk-cache writes, device-step errors, prep-thread death, page-alloc
    failure) and publishes the injected-fault and recovery-event counts;
    the leg itself asserts exactly-once token-identical completion,
    fault->event matching, and >= 70% of fault-free throughput."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serve_traffic as st

    args = argparse.Namespace(requests=250, slots=4, max_len=96,
                              page_size=16, rate=300.0)
    cfg = api.configs.get("llama3-8b").scaled(
        d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab=97, dtype="float32")
    model = api.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    results = st.bench_faults(args, cfg, model, params)
    injected = results["faulted"]["faults_injected"]
    recovered = results["faulted"]["recovery_events"]
    emit("chaos_fault_classes", 0.0, len(injected))
    emit("chaos_faults_injected", 0.0, sum(injected.values()))
    emit("chaos_recovery_events", 0.0, sum(recovered.values()))
    emit("chaos_requests_exactly_once", 0.0, args.requests)
    emit("chaos_retries", 0.0, results["faulted"]["retries"])
    emit("chaos_quarantine_clears", 0.0,
         results["faulted"]["quarantine_stats"]["clears"])
    emit("chaos_throughput_ratio", 0.0, results["faulted_throughput_ratio"])


def bench_autotune() -> None:
    """Measured-feedback autotuning (tune/ + measure-mode explore).

    Three legs over one temp cache+DB directory:

    * **measure** — a cold sweep with ``measure=3`` wall-times candidate
      tilings per default-corpus workload (pallas-interpret; tile sizes
      change the interpreted grid, so the signal is real) and records
      every measurement into the tuning DB.  The measured winner must be
      no worse than the analytic choice everywhere (the analytic tiling
      is always candidate 0, so the min over candidates can't lose) and
      strictly better somewhere.
    * **replay** — a fresh cache instance over the same directory (= a
      new process) compiles each workload with ``tune=db``: every record
      must carry ``decision_source == "tuned"``, the DB must not grow
      (replay never re-measures), and the tuned fig4 conv must stay
      bit-exact vs the reference interpreter.
    * **calibrate** — profiled jnp compiles append (predicted, measured)
      residual rows; a per-term calibration is fit from them, activated,
      persisted next to the DB, and a second profiled pass must shrink
      the |log gmean(measured/predicted)| bias.

    Artifacts ``tuning_db.json`` and ``calibration_report.json`` are
    copied into the CWD for CI upload.
    """
    import math
    import shutil
    import tempfile

    from repro.tune import clear_calibrations, save_calibrations

    rng = np.random.RandomState(0)
    space = api.get_space("tpu-sweep")
    hw = space.base_config()
    workloads = api.get_workloads("default")

    def rand_inputs(prog):
        ins = {}
        for nm in prog.inputs:
            d = prog.buffers[nm]
            ins[nm] = (rng.randint(-4, 5, d.shape).astype(np.int8)
                       if d.dtype == "int8"
                       else rng.randn(*d.shape).astype(np.float32))
        return ins

    with tempfile.TemporaryDirectory() as d:
        db = api.TuningDB(dir=d)

        # ---- leg 1: cold sweep + measure populates the DB -----------------
        t0 = time.perf_counter()
        sweep = api.run_sweep(space, "default", budget=4, strategy="grid",
                              cache_dir=d, measure=3, tune_db=db)
        dt_measure = (time.perf_counter() - t0) * 1e6
        ms = sweep.measurement
        assert ms is not None
        assert len(db) == len(workloads), (len(db), ms["workloads"])
        no_worse = better = 0
        for name, wl in sorted(ms["workloads"].items()):
            assert not wl.get("error"), f"{name}: {wl['error']}"
            speed = wl.get("speedup_vs_analytic") or 1.0
            if wl["best_s"] <= wl["analytic_s"] * 1.05:
                no_worse += 1
            if wl["improved"]:
                better += 1
            emit(f"autotune_measured/{name}", wl["best_s"] * 1e6,
                 f"\"{speed:.2f}x vs analytic "
                 f"({wl['n_candidates']} cands, {wl['n_rejected']} rejected)\"")
        assert no_worse >= 3 and better >= 1, (no_worse, better)
        emit("autotune_measure_sweep", dt_measure,
             f"\"db={len(db)} no_worse={no_worse}/{len(workloads)} "
             f"better={better}\"")

        # ---- leg 2: tuned replay from a fresh cache (= new process) -------
        n_before = len(db)
        cache2 = api.CompilationCache(disk_dir=d)
        t0 = time.perf_counter()
        tuned_recs = {}
        for w in workloads:
            c = api.stripe_jit(w.build(), hw, backend="pallas",
                               cache=cache2, tune=db)
            tuned_recs[w.name] = c
        dt_replay = (time.perf_counter() - t0) * 1e6 / len(workloads)
        n_tuned = sum(1 for c in tuned_recs.values()
                      if c.record.decision_source == "tuned")
        assert n_tuned == len(workloads), {
            n: c.record.decision_source for n, c in tuned_recs.items()}
        assert cache2.stats.tuned_hits == len(workloads)
        assert len(db) == n_before, "tuned replay must not re-measure"
        best = {n: wl["best_candidate"] for n, wl in ms["workloads"].items()}
        assert all(c.record.tuned["candidate_id"] == best[n]
                   for n, c in tuned_recs.items())
        # the replayed winner stays correct: int8 fig4 conv is bit-exact
        fig4 = next(w for w in workloads if w.name == "fig4_conv")
        src = fig4.build()
        ins = rand_inputs(src)
        got = np.asarray(tuned_recs["fig4_conv"](ins)["O"])
        assert (got == api.execute_reference(src, ins)["O"]).all()
        emit("autotune_tuned_replay_compile", dt_replay,
             f"\"{n_tuned}/{len(workloads)} tuned "
             f"(hits={cache2.stats.tuned_hits})\"")

        # DB round-trip: a fresh handle sees identical entries
        db2 = api.TuningDB(dir=d)
        assert len(db2) == n_before
        for w in workloads:
            rec = tuned_recs[w.name].record
            e = db2.lookup(rec.ir_fingerprint, rec.hw_fingerprint,
                           "pallas", True)
            assert e is not None and e.candidate_id == best[w.name]
        emit("autotune_db_roundtrip", 0.0, n_before)
        shutil.copyfile(db.path, "tuning_db.json")

        # ---- leg 3: online cost-model calibration -------------------------
        clear_calibrations()
        try:
            cache3 = api.CompilationCache(disk_dir=d)
            for _pass in range(2):
                for w in workloads:
                    prog = w.build()
                    c = api.stripe_jit(prog, hw, backend="jnp",
                                       profile=True, cache=cache3)
                    c(rand_inputs(prog))
                if _pass == 0:
                    rows = obs.read_residuals(obs.residual_log_path(cache3))
                    fit = api.fit_calibration(rows, hw.fingerprint(), "jnp")
                    assert fit is not None, "calibration fit needs term rows"
                    api.set_calibration(fit)
                    save_calibrations(d, cals=[fit])  # persist next to the DB
            rows = obs.read_residuals(obs.residual_log_path(cache3))

            def gmean(rs):
                logs = [math.log(r["measured_s"] / r["predicted_s"])
                        for r in rs if r.get("predicted_s")
                        and r.get("measured_s")]
                return math.exp(sum(logs) / len(logs)) if logs else None

            g_before = gmean([r for r in rows if not r.get("calibrated")])
            g_after = gmean([r for r in rows if r.get("calibrated")])
            assert g_before is not None and g_after is not None
            bias_b, bias_a = abs(math.log(g_before)), abs(math.log(g_after))
            assert bias_a <= bias_b, (g_before, g_after)
            with open("calibration_report.json", "w") as f:
                json.dump({"hw": hw.name, "backend": "jnp",
                           "rows": len(rows),
                           "gmean_before": g_before, "gmean_after": g_after,
                           "bias_before": bias_b, "bias_after": bias_a,
                           "calibration": fit.to_json()}, f, indent=2)
            emit("autotune_calibration_gmean_before", 0.0, f"{g_before:.3f}")
            emit("autotune_calibration_gmean_after", 0.0, f"{g_after:.3f}")
            emit("autotune_calibration_bias_shrink", 0.0,
                 f"{bias_b / max(bias_a, 1e-9):.1f}x")
        finally:
            clear_calibrations()


def bench_distributed() -> None:
    """Multi-device smoke on the devices present (8 emulated host devices
    on the CPU, see ``main``): the acceptance FFN through
    ``stripe_jit(mesh=n)`` vs the *replicated* placement on the same mesh
    (every device computes the full program — the no-partitioning
    baseline; emulated devices share the host cores, so there the
    wall-clock ratio measures the partition's per-device work reduction,
    not physical parallelism), plus the predicted-vs-emitted collective
    loop on a reduction-split matmul (psum count and modelled bytes
    asserted).  A plain single-device row is emitted as the absolute
    reference.  Runs in this process: one process holds the chips."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import mesh_lower
    from repro.core.cost import collective_seconds

    n = min(jax.device_count(), 8)
    if n < 2:
        emit("distributed_skipped", 0.0, f"\"only {n} device(s)\"")
        return
    hw = api.get_config("cpu_test")

    def ffn(m, k, n_out):
        tp = api.TileProgram("ffn")
        tp.input("X", (m, k), "float32")
        tp.input("W", (k, n_out), "float32")
        tp.input("B", (n_out,), "float32")
        tp.output("O", (m, n_out), "float32")
        tp.temp("T", (m, n_out), "float32")
        tp.temp("U", (m, n_out), "float32")
        tp.op("T[i, j] += X[i, c] * W[c, j]", name="mm")
        tp.op("U[i, j] = T[i, j] + B[j]", name="bias")
        tp.op("O[i, j] = gelu(U[i, j])", name="act")
        return tp.build()

    m, k, n_out = 2048, 512, 512
    rng = np.random.default_rng(0)
    arrays = {"X": rng.normal(size=(m, k)).astype("float32"),
              "W": rng.normal(size=(k, n_out)).astype("float32"),
              "B": rng.normal(size=(n_out,)).astype("float32")}
    single = api.jit(ffn(m, k, n_out), hw, backend="jnp")
    sh = api.jit(ffn(m, k, n_out), hw, backend="jnp", mesh=n)

    # replicated placement on the same mesh: every device runs the full
    # single-device program (in_specs/out_specs all P())
    jmesh = Mesh(np.array(jax.devices()[:n]), ("x",))
    inner = api.jit(ffn(m, k, n_out), hw, backend="jnp", jit=False)
    in_order = ["X", "W", "B"]
    rep_jit = jax.jit(jax.shard_map(
        lambda X, W, B: inner({"X": X, "W": W, "B": B})["O"],
        mesh=jmesh, in_specs=(P(), P(), P()), out_specs=P(), check_vma=False))
    rep = lambda a: {"O": rep_jit(*[a[name] for name in in_order])}  # noqa: E731

    r0, s0, g0 = rep(arrays), sh(arrays), single(arrays)
    np.testing.assert_allclose(np.asarray(s0["O"]), np.asarray(g0["O"]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(r0["O"]), np.asarray(g0["O"]),
                               rtol=1e-4, atol=1e-4)

    def best_us(fn, rounds=5):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arrays)["O"])
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    t_single, t_rep, t_sh = best_us(single), best_us(rep), best_us(sh)

    # predicted-vs-emitted collective loop: reduction-split matmul
    tp = api.TileProgram("kred")
    tp.input("X", (12, 4096), "float32")
    tp.input("W", (4096, 20), "float32")
    tp.output("O", (12, 20), "float32")
    tp.op("O[i, j] += X[i, c] * W[c, j]", name="mm")
    kr = api.jit(tp.build(), hw, backend="jnp", mesh=n)
    karr = {"X": rng.normal(size=(12, 4096)).astype("float32"),
            "W": rng.normal(size=(4096, 20)).astype("float32")}
    counts = mesh_lower.count_collectives(kr._fn, karr)
    assert counts.get("psum") == 1, counts
    pred = kr.record.mesh["collective_bytes"]
    want = collective_seconds("psum", 12 * 20 * 4, n, 1.0)
    assert abs(pred - want) < 1e-6, (pred, want)
    np.testing.assert_allclose(np.asarray(kr(karr)["O"]),
                               np.asarray(karr["X"] @ karr["W"]),
                               rtol=1e-3, atol=1e-3)

    speedup = t_rep / t_sh
    emit("distributed_devices", 0.0, n)
    emit("distributed_ffn_single_device", t_single, "")
    emit(f"distributed_ffn_replicated_mesh{n}", t_rep, "")
    emit(f"distributed_ffn_sharded_mesh{n}", t_sh, f"{speedup:.2f}x")
    assert speedup > 1.0, \
        f"sharded must beat the replicated placement ({speedup:.2f}x)"
    emit("distributed_ffn_collective_bytes", 0.0,
         int(sh.record.mesh["collective_bytes"]))
    emit("distributed_kred_psum_emitted_vs_predicted", 0.0,
         f"\"psum={counts['psum']} bytes={int(pred)}\"")


BENCHES = {
    "fig1": bench_fig1_engineering_effort,
    "fig4": bench_fig4_autotile,
    "fig5": bench_fig5_rewrite,
    "cache": bench_stripe_jit_cache,
    "fusion": bench_fusion,
    "memplan": bench_memplan,
    "conv": bench_conv,
    "explore": bench_explore,
    "distributed": bench_distributed,
    "autotune": bench_autotune,
    "serving": bench_serving,
    "chaos": bench_chaos,
    "matmul": bench_stripe_matmul,
    "flash": bench_flash_attention_blocks,
    "hillclimb": bench_hillclimb,
    "arch": bench_arch_steps,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="OUT.json", default=None,
                    help="also write records as JSON to this path")
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {','.join(BENCHES)}")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="enable span tracing for the whole run and write a "
                         "Chrome/Perfetto trace at the end")
    ap.add_argument("--metrics", metavar="OUT.json", default=None,
                    help="write the process-wide metrics-registry snapshot "
                         "at the end")
    ap.add_argument("--profile", action="store_true",
                    help="use profiled Stripe compiles (measured per-unit "
                         "latencies + residual log) in the cache/serving "
                         "benches")
    args = ap.parse_args(argv)
    if "device_count" not in os.environ.get("XLA_FLAGS", ""):
        # the distributed leg's mesh on the CPU: 8 emulated host devices,
        # set before the first JAX call initializes the backend
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=8")
    api.enable_compilation_cache(REPO)
    global PROFILE
    PROFILE = args.profile
    if args.trace:
        obs.enable_tracing()

    selected = list(BENCHES)
    if args.only:
        selected = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in selected if s not in BENCHES]
        if unknown:
            ap.error(f"unknown bench(es) {unknown}; choose from {list(BENCHES)}")
    if args.json:
        # fail on an unwritable path now, not after minutes of benching
        try:
            open(args.json, "a").close()
        except OSError as e:
            ap.error(f"cannot write --json path: {e}")
    for name in selected:
        BENCHES[name]()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(RESULTS, f, indent=2)
        print(f"# wrote {len(RESULTS)} records to {args.json}")
    if args.trace:
        obs.export_chrome_trace(args.trace)
        print(f"# wrote {args.trace} ({len(obs.spans())} spans)")
    if args.metrics:
        with open(args.metrics, "w") as f:
            json.dump(obs.metrics_snapshot(), f, indent=2)
        print(f"# wrote {args.metrics}")


if __name__ == "__main__":
    main()
