"""Halo-aware Pallas lowering + per-block hybrid fallback.

* the paper's Fig. 4/5 conv lowers to real ``pallas_call`` kernels
  (halo views over materialized operands, constraints as masked stores)
  and matches the reference interpreter — bit-exact for the int8 Fig. 4
  program;
* interior + boundary pieces partition the iteration space exactly
  (hypothesis property over random conv shapes, reference-interpreter
  equality), and the ``boundary`` pass splits *every* constraint-carrying
  grid axis under the per-index budget;
* a non-dividing tile's boundary remainder takes the masked-store path
  while the interior piece lowers densely;
* a program containing one unsupported block keeps its other groups as
  Pallas kernels, with per-unit backend + fallback reason on the
  ``CompileRecord``.
"""
import copy

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import TileProgram, execute_reference, stripe_jit
from repro.core.frontend import single_op_program
from repro.core.hwconfig import get_config
from repro.core.ir import Program
from repro.core.lower_pallas import lower_program_hybrid
from repro.core.passes.boundary import split_boundary, _n_constraints
from repro.core.tiling import split_block


def _conv_prog(x, y, c, k, f, dtype="float32", name="conv"):
    pad = f // 2
    return single_op_program(
        f"O[x, y, k] += I[x + i - {pad}, y + j - {pad}, c] * F[i, j, c, k]",
        {"I": ((x, y, c), dtype), "F": ((f, f, c, k), dtype),
         "O": ((x, y, k), dtype if dtype != "int8" else "int32")},
        out="O", name=name)


def _conv_inputs(prog, seed=0):
    rng = np.random.RandomState(seed)
    out = {}
    for n in prog.inputs:
        d = prog.buffers[n]
        if d.dtype == "int8":
            out[n] = rng.randint(-4, 5, d.shape).astype(np.int8)
        else:
            out[n] = rng.randn(*d.shape).astype(np.float32)
    return out


# --------------------------------------------------------------- fig4 / fig5
def test_fig5_conv_lowers_to_pallas_and_matches_reference():
    """The acceptance bar: the paper's conv runs as real pallas_calls (no
    whole-program fallback) and pallas-interpret output matches the
    reference interpreter."""
    from repro.explore.workloads import fig5_conv_f32

    prog = fig5_conv_f32()
    src = copy.deepcopy(prog)
    compiled = stripe_jit(prog, get_config("tpu_v5e"), backend="pallas",
                          interpret=True, use_disk=False)
    rec = compiled.record
    assert rec.backend == "pallas", rec.fallback_reason
    assert rec.n_kernels >= 1
    assert set(rec.block_backends.values()) == {"pallas"}
    assert rec.fallback_reasons() == {}
    ins = _conv_inputs(src)
    got = np.asarray(compiled(ins)["O"])
    want = execute_reference(src, ins)["O"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fig4_conv_int8_is_bit_exact():
    from repro.explore.workloads import fig4_conv

    prog = fig4_conv()
    src = copy.deepcopy(prog)
    compiled = stripe_jit(prog, get_config("tpu_v5e"), backend="pallas",
                          interpret=True, use_disk=False)
    assert compiled.record.backend == "pallas", compiled.record.fallback_reason
    assert compiled.record.n_kernels >= 1
    ins = _conv_inputs(src, 1)
    got = np.asarray(compiled(ins)["O"])
    want = execute_reference(src, ins)["O"]
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------- partition properties
@settings(max_examples=8, deadline=None)
@given(st.integers(5, 10), st.integers(4, 9), st.integers(1, 2),
       st.integers(1, 2), st.sampled_from([2, 3]), st.integers(2, 4),
       st.sampled_from(["remainder", "edges"]))
def test_boundary_pieces_partition_conv_iteration_space(
        x, y, c, k, f, tile, mode):
    """Interior + boundary pieces partition the iteration space exactly:
    executing the piece list reproduces the unsplit conv on random
    shapes/filters/tiles (non-dividing tiles included)."""
    prog = _conv_prog(x, y, c, k, f)
    src = copy.deepcopy(prog)
    blk = prog.entry.stmts[0]
    outer = split_block(blk, {"x": tile, "y": tile})
    pieces = split_boundary(outer, mode=mode, max_splits=4)
    prog.entry.stmts = list(pieces)
    ins = _conv_inputs(src, seed=x * 100 + y * 10 + f)
    want = execute_reference(src, ins)["O"]
    got = execute_reference(prog, ins)["O"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # piece names are deterministic segment-start keys
    assert len({p.name for p in pieces}) == len(pieces)


def test_per_index_budget_splits_both_conv_axes():
    """The old global max_splits budget could starve later indices; the
    per-index budget splits every constraint-carrying grid axis, yielding
    a constraint-free (tagged) interior on a 2-D-tiled conv."""
    prog = _conv_prog(32, 32, 2, 2, 3, name="conv2d")
    blk = prog.entry.stmts[0]
    outer = split_block(blk, {"x": 8, "y": 8})
    pieces = split_boundary(outer, mode="edges", max_splits=4)
    split_axes = {seg[0] for p in pieces for seg in p.name.split(".")
                  if len(seg) > 1 and seg[0] in "xy" and seg[1:].isdigit()}
    assert {"x", "y"} <= split_axes
    interior = [p for p in pieces if "interior" in p.tags]
    assert interior, "no constraint-free interior piece"
    assert all(_n_constraints(p) == 0 for p in interior)
    for p in pieces:
        assert ("interior" in p.tags) != ("boundary" in p.tags)


def test_masked_remainder_non_dividing_tile():
    """A matmul tiled 8 over m=12: the interior piece lowers densely, the
    overflow remainder takes the masked-store path, and the composed
    kernels reproduce the reference."""
    tp = TileProgram("mmrem")
    tp.input("A", (12, 8))
    tp.input("B", (8, 16))
    tp.output("O", (12, 16))
    tp.op("O[m, n] += A[m, c] * B[c, n]", name="mm")
    prog = tp.build()
    src = copy.deepcopy(prog)
    blk = prog.entry.stmts[0]
    outer = split_block(blk, {"m": 8})  # 12 % 8 != 0 -> overflow constraint
    pieces = split_boundary(outer)
    assert any("interior" in p.tags for p in pieces)
    assert any("boundary" in p.tags for p in pieces)
    prog.entry.stmts = list(pieces)
    prog.source = copy.deepcopy(src)
    fn = lower_program_hybrid(prog, interpret=True)
    assert fn.n_pallas == len(pieces)  # both pieces are real kernels
    ins = {"A": np.random.RandomState(3).randn(12, 8).astype(np.float32),
           "B": np.random.RandomState(4).randn(8, 16).astype(np.float32)}
    got = np.asarray(fn(ins)["O"])
    want = execute_reference(src, ins)["O"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_kernel_names_are_stable_across_lowerings():
    """Kernel names come from the program and its units alone: two
    lowerings of one program name their kernels alike, and the boundary
    piece of a unit is told apart from its interior."""
    def lowered():
        tp = TileProgram("mmrem")
        tp.input("A", (12, 8))
        tp.input("B", (8, 16))
        tp.output("O", (12, 16))
        tp.op("O[m, n] += A[m, c] * B[c, n]", name="mm")
        prog = tp.build()
        src = copy.deepcopy(prog)
        prog.entry.stmts = list(split_boundary(split_block(prog.entry.stmts[0],
                                                           {"m": 8})))
        prog.source = src
        return lower_program_hybrid(prog, interpret=True)

    first, second = lowered(), lowered()
    assert first.kernel_names == ["mmrem.mm", "mmrem.mm_p1"]
    assert second.kernel_names == first.kernel_names


@settings(max_examples=6, deadline=None)
@given(st.integers(4, 8), st.integers(4, 8), st.integers(1, 3),
       st.integers(1, 3), st.sampled_from([2, 3]))
def test_property_conv_pallas_interpret_matches_reference(x, y, c, k, f):
    """End-to-end: random conv shapes through the full tpu_v5e pipeline +
    pallas-interpret equal the reference interpreter."""
    prog = _conv_prog(x, y, c, k, f)
    src = copy.deepcopy(prog)
    compiled = stripe_jit(prog, get_config("tpu_v5e"), backend="pallas",
                          interpret=True, use_disk=False)
    assert compiled.record.backend == "pallas", compiled.record.fallback_reason
    ins = _conv_inputs(src, seed=x * 1000 + y * 100 + c * 10 + f)
    got = np.asarray(compiled(ins)["O"])
    want = execute_reference(src, ins)["O"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- per-block hybrid
def _mixed_prog():
    tp = TileProgram("mixed")
    tp.input("A", (16, 8))
    tp.input("B", (8, 16))
    tp.temp("T", (16, 16))
    tp.output("O2", (16, 16))
    tp.output("M", (16,))
    tp.op("T[i, j] += A[i, c] * B[c, j]", name="mm")
    tp.op("O2[i, j] = gelu(T[i, j])", name="act")
    tp.op("M[i] max= T[i, j]", name="rowmax")  # max-agg: no Pallas path
    return tp.build()


def test_hybrid_keeps_pallas_kernels_next_to_fallback_block():
    """One unsupported block (max-aggregation) no longer costs the whole
    program its kernels: the other groups stay Pallas and the record
    carries per-unit backend + reason."""
    prog = _mixed_prog()
    src = copy.deepcopy(prog)
    compiled = stripe_jit(prog, get_config("tpu_v5e"), backend="pallas",
                          interpret=True, use_disk=False)
    rec = compiled.record
    assert rec.backend == "pallas"
    assert rec.block_backends["rowmax"] == "jnp"
    pallas_units = [u for u, b in rec.block_backends.items() if b == "pallas"]
    assert pallas_units, rec.block_backends
    assert "rowmax" in rec.fallback_reasons()
    # satellite: BOTH attempted paths' reasons are recorded, not only the
    # contraction error
    reason = rec.fallback_reasons()["rowmax"]
    assert "contraction:" in reason and "windowed:" in reason
    ins = {"A": np.random.RandomState(0).randn(16, 8).astype(np.float32),
           "B": np.random.RandomState(1).randn(8, 16).astype(np.float32)}
    got = compiled(ins)
    want = execute_reference(src, ins)
    for out in ("O2", "M"):
        np.testing.assert_allclose(np.asarray(got[out]), want[out],
                                   rtol=1e-4, atol=1e-5)


def test_two_accumulating_writers_refuse_hybrid_and_aggregate():
    """Two ``+=`` writers into one buffer cannot be composed by region
    placement: the hybrid refuses (whole-program fallback, reason
    recorded) and the jnp path aggregates the second writer's
    contribution with the first instead of clobbering it."""
    tp = TileProgram("twowrite")
    tp.input("A", (8, 4))
    tp.input("B", (4, 8))
    tp.input("C", (8, 4))
    tp.input("D", (4, 8))
    tp.output("O", (8, 8))
    tp.op("O[i, j] += A[i, k] * B[k, j]", name="mm1")
    tp.op("O[i, j] += C[i, k] * D[k, j]", name="mm2")
    prog = tp.build()
    src = copy.deepcopy(prog)
    rng = np.random.RandomState(7)
    ins = {n: rng.randn(*src.buffers[n].shape).astype(np.float32)
           for n in src.inputs}
    want = execute_reference(src, ins)["O"]
    for backend in ("jnp", "pallas"):
        compiled = stripe_jit(copy.deepcopy(src), get_config("tpu_v5e"),
                              backend=backend, interpret=True, use_disk=False)
        assert compiled.record.backend == "jnp"
        np.testing.assert_allclose(np.asarray(compiled(ins)["O"]), want,
                                   rtol=1e-4, atol=1e-5)
    assert "writes to O" in compiled.record.fallback_reason \
        or "write O" in compiled.record.fallback_reason


def test_whole_program_fallback_still_records_reason():
    """When every unit falls back the record degrades to backend=jnp with
    the per-unit reasons surfaced."""
    tp = TileProgram("allmax")
    tp.input("X", (8, 8))
    tp.output("M", (8,))
    tp.op("M[i] max= X[i, j]", name="colmax")
    compiled = stripe_jit(tp.build(), get_config("tpu_v5e"), backend="pallas",
                          interpret=True, use_disk=False)
    rec = compiled.record
    assert rec.backend == "jnp"
    assert rec.block_backends == {"colmax": "jnp"}
    assert "colmax" in rec.fallback_reasons()


def test_memplan_prices_halo_slots():
    """The memory plan classifies a conv's overlapped input as a ``halo``
    slot and prices the margin bytes (slot = tile core + margin)."""
    from repro.core import memplan
    from repro.core.hwconfig import get_config
    from repro.core.passes import PassManager

    prog = _conv_prog(12, 16, 8, 16, 3, name="fig5")
    opt = PassManager(get_config("tpu_v5e")).run(prog)
    grids = [s for s in opt.entry.stmts
             if isinstance(s, type(opt.entry)) and "grid" in s.tags]
    assert grids
    plan = memplan.plan_block(grids[0], depth=2)
    halo_slots = [a for a in plan.allocs if a.view.kind == "halo"]
    assert halo_slots, [a.view.kind for a in plan.allocs]
    assert plan.halo_bytes > 0
    # the conv's I view: (10, 18, 8) extent over an (8, 16, 8) core
    assert any(a.view.halo_bytes == (10 * 18 * 8 - 8 * 18 * 8) * 4
               for a in halo_slots)


def test_autotile_charges_halo_traffic():
    """The roofline model charges halo materialization/refetch bytes, so
    a larger tile along the halo axis amortizes the overlap."""
    from repro.core.cost import evaluate_tiling

    prog = _conv_prog(64, 64, 4, 8, 3, name="conv64")
    blk = prog.entry.stmts[0]
    hw = get_config("tpu_v5e")
    params = dict(hw.passes[1][1])
    small = evaluate_tiling(blk, {"x": 4, "y": 4}, hw, params)
    big = evaluate_tiling(blk, {"x": 16, "y": 16}, hw, params)
    assert small.halo_bytes > big.halo_bytes > 0
    # a non-halo matmul charges nothing
    tp = TileProgram("mm")
    tp.input("A", (64, 64))
    tp.input("B", (64, 64))
    tp.output("O", (64, 64))
    tp.op("O[i, j] += A[i, c] * B[c, j]", name="mm")
    mm = tp.build().entry.stmts[0]
    assert evaluate_tiling(mm, {"i": 16, "j": 16}, hw, params).halo_bytes == 0
