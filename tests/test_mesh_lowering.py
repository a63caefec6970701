"""Multi-device lowering: ``stripe_jit(..., mesh=)`` through shard_map.

The ``distributed``-marked tests run **in process** on the 8 emulated
host devices conftest forces before jax initializes; the plan-level and
explore tests touch no devices at all.  Every device test closes the
predicted-vs-emitted loop: the collectives the shard plan priced are the
collective primitives the jaxpr actually contains
(``count_collectives`` == ``expected_primitive_counts``).
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mesh_lower
from repro.core.cost import collective_seconds, score_pass_trace
from repro.core.driver import compile_cached, stripe_jit
from repro.core.frontend import TileProgram
from repro.core.hwconfig import CPU_TEST, TPU_V5E
from repro.core.shardplan import UnsupportedMesh, plan_program

distributed = pytest.mark.distributed

# The sharded conv sums the same 3*3*c products per output point as the
# single-device one, but XLA:CPU picks its dot reduction order from the
# operand shape, and a shard has fewer rows than the whole array: the
# sums round differently in the last bits (up to 3e-6 measured on
# N(0, 1) data, values up to ~10).
HALO_RTOL = 1e-5
HALO_ATOL = 1e-5


# --------------------------------------------------------------------------
# workloads
# --------------------------------------------------------------------------
def ffn(m=256, k=64, n=64):
    tp = TileProgram("ffn")
    tp.input("X", (m, k), "float32")
    tp.input("W", (k, n), "float32")
    tp.input("B", (n,), "float32")
    tp.output("O", (m, n), "float32")
    tp.temp("T", (m, n), "float32")
    tp.temp("U", (m, n), "float32")
    tp.op("T[i, j] += X[i, c] * W[c, j]", name="mm")
    tp.op("U[i, j] = T[i, j] + B[j]", name="bias")
    tp.op("O[i, j] = gelu(U[i, j])", name="act")
    return tp.build()


def matmul(m, k, n):
    tp = TileProgram("mm")
    tp.input("X", (m, k), "float32")
    tp.input("W", (k, n), "float32")
    tp.output("O", (m, n), "float32")
    tp.op("O[i, j] += X[i, c] * W[c, j]", name="mm")
    return tp.build()


def halo_conv(x=32, y=15, c=5, k=7):
    tp = TileProgram("conv")
    tp.input("I", (x, y, c), "float32")
    tp.input("F", (3, 3, c, k), "float32")
    tp.output("O", (x, y, k), "float32")
    tp.op("O[x, y, k] += I[x + i - 1, y + j - 1, c] * F[i, j, c, k]",
          name="conv")
    return tp.build()


def mlp2(m=12, c=24, h=4096, f=64):
    """Two chained matmuls whose only divisible dims are the hidden
    contraction ``h`` and the ring-eligible ``f`` — forces a
    reduction split on mm2 (psum or ring, by cost)."""
    tp = TileProgram("mlp2")
    tp.input("X", (m, c), "float32")
    tp.input("W1", (c, h), "float32")
    tp.input("W2", (h, f), "float32")
    tp.output("O", (m, f), "float32")
    tp.temp("H", (m, h), "float32")
    tp.op("H[i, h] += X[i, c] * W1[c, h]", name="mm1")
    tp.op("O[i, f] += H[i, h] * W2[h, f]", name="mm2")
    return tp.build()


def _arrays(prog, seed=0):
    rng = np.random.default_rng(seed)
    return {name: rng.normal(size=prog.buffers[name].shape).astype("float32")
            for name in prog.inputs}


def _assert_predicted_collectives(compiled, arrays):
    """The plan's predicted collective primitives must equal the emitted
    jaxpr's, and the recorded bytes must equal the interconnect model's
    per-device moved bytes for those collectives."""
    plan_counts = {}
    for c in compiled.record.mesh["collectives"]:
        # record -> primitive name (ring = ppermute loop + gather)
        if c["collective"] == "ring_matmul":
            for p in ("ppermute", "all_gather"):
                plan_counts[p] = plan_counts.get(p, 0) + 1
        elif c["collective"] == "halo":
            pass  # counted via lo/hi below
        else:
            p = c["collective"]
            plan_counts[p] = plan_counts.get(p, 0) + 1
    got = mesh_lower.count_collectives(compiled._fn, arrays)
    for prim, n in plan_counts.items():
        assert got.get(prim, 0) >= n, (prim, plan_counts, got)
    total = sum(c["bytes"] for c in compiled.record.mesh["collectives"])
    assert total == compiled.record.mesh["collective_bytes"]
    assert total > 0


# --------------------------------------------------------------------------
# device tests (8 emulated host devices, in process)
# --------------------------------------------------------------------------
@distributed
def test_ffn_mesh8_pallas_matches_single_device():
    """The acceptance workload: matmul -> bias -> gelu compiled through
    shard_map on 8 devices with per-shard Pallas (interpret) kernels,
    output-split, exact against the single-device lowering."""
    prog = ffn()
    arrays = _arrays(prog)
    ref = stripe_jit(ffn(), CPU_TEST, backend="jnp")(arrays)
    c = stripe_jit(ffn(), CPU_TEST, backend="pallas", interpret=True, mesh=8)
    out = c(arrays)
    np.testing.assert_allclose(out["O"], ref["O"], rtol=1e-5, atol=1e-5)

    rec = c.record
    assert rec.backend == "pallas"          # per-shard kernels are Pallas
    assert rec.mesh["n_devices"] == 8
    assert rec.mesh["shape"] == [8]
    assert rec.mesh["splits"]               # at least the seed block split
    assert rec.mesh["segments"], "segments carry their own compile records"
    for seg in rec.mesh["segments"]:
        assert seg["backend"] == "pallas"
    # predicted == emitted
    counts = mesh_lower.count_collectives(c._fn, arrays)
    assert counts == mesh_lower.expected_primitive_counts_from_record(rec.mesh)
    _assert_predicted_collectives(c, arrays)
    # the sharded-output gather moves (n-1)/n of the output per device
    n = 8
    out_bytes = 256 * 64 * 4
    assert rec.mesh["collective_bytes"] == pytest.approx(
        collective_seconds("all_gather", out_bytes, n, 1.0))


@distributed
def test_reduction_split_psum_tolerance_exact():
    """A matmul whose only divisible index is the contraction: the plan
    must emit full-shape partials + one psum, tolerance-exact in f32."""
    prog = matmul(12, 64, 20)
    arrays = _arrays(prog)
    ref = stripe_jit(matmul(12, 64, 20), CPU_TEST, backend="jnp")(arrays)
    c = stripe_jit(matmul(12, 64, 20), CPU_TEST, backend="jnp", mesh=8)
    out = c(arrays)
    np.testing.assert_allclose(out["O"], ref["O"], rtol=1e-5, atol=1e-5)
    counts = mesh_lower.count_collectives(c._fn, arrays)
    assert counts.get("psum") == 1
    ops = [col["collective"] for col in c.record.mesh["collectives"]]
    assert ops == ["psum"]
    # psum of the (12, 20) f32 partials: 2(n-1)/n of the payload moves
    assert c.record.mesh["collective_bytes"] == pytest.approx(
        collective_seconds("psum", 12 * 20 * 4, 8, 1.0))


@distributed
def test_halo_conv_bit_exact():
    """A 3x3 conv split on the spatial x dim: boundary slabs move by
    ppermute (zero-filled at the ends — exactly the dropped frontend
    boundary constraints), bit-exact against single-device."""
    prog = halo_conv()
    arrays = _arrays(prog)
    ref = stripe_jit(halo_conv(), CPU_TEST, backend="jnp")(arrays)
    c = stripe_jit(halo_conv(), CPU_TEST, backend="jnp", mesh=8)
    out = c(arrays)
    np.testing.assert_allclose(np.asarray(out["O"]), np.asarray(ref["O"]),
                               rtol=HALO_RTOL, atol=HALO_ATOL)
    counts = mesh_lower.count_collectives(c._fn, arrays)
    assert counts.get("ppermute") == 2      # lo + hi margins
    assert counts.get("all_gather") == 1    # sharded output
    ops = sorted(col["collective"] for col in c.record.mesh["collectives"])
    assert ops == ["all_gather", "halo"]


@distributed
def test_ring_overlap_chosen_by_cost():
    """The gather/compute-interleaved ring matmul is the schedule's
    overlap primitive — chosen by the interconnect model, not by hand:
    slow links + slow compute pick the ring, stock links pick psum.
    Both are numerically correct."""
    prog = mlp2()
    arrays = _arrays(prog)
    ref = stripe_jit(mlp2(), CPU_TEST, backend="jnp")(arrays)

    slow = dataclasses.replace(TPU_V5E, ici_link_bw=1e7, peak_flops=1e8)
    c_ring = stripe_jit(mlp2(), slow, backend="jnp", mesh=8)
    assert c_ring.record.mesh["overlapped"], "expected ring overlap"
    ops = [col["collective"] for col in c_ring.record.mesh["collectives"]]
    assert "ring_matmul" in ops
    out = c_ring(arrays)
    # the ring adds the 4096-term contraction as 8 partial sums in another
    # order than one dot: f32 reassociation error scales with the size of
    # the terms, not of the result, so an output near zero by cancellation
    # needs an absolute bound tied to the output's scale (~30 ulps of it)
    atol = 2e-6 * float(np.abs(ref["O"]).max())
    np.testing.assert_allclose(out["O"], ref["O"], rtol=1e-4, atol=atol)
    counts = mesh_lower.count_collectives(c_ring._fn, arrays)
    assert counts == mesh_lower.expected_primitive_counts_from_record(
        c_ring.record.mesh)

    c_psum = stripe_jit(mlp2(), TPU_V5E, backend="jnp", mesh=8)
    ops = [col["collective"] for col in c_psum.record.mesh["collectives"]]
    assert "psum" in ops and "ring_matmul" not in ops
    assert not c_psum.record.mesh["overlapped"]
    out = c_psum(arrays)
    np.testing.assert_allclose(out["O"], ref["O"], rtol=1e-4, atol=atol)


@distributed
def test_mesh_fallback_indivisible():
    """No divisible index -> single-device compile, reason recorded."""
    prog = matmul(13, 7, 5)
    arrays = _arrays(prog)
    c = stripe_jit(matmul(13, 7, 5), CPU_TEST, backend="jnp", mesh=8)
    assert "fallback" in c.record.mesh
    assert "divisible" in c.record.mesh["fallback"]
    ref = stripe_jit(matmul(13, 7, 5), CPU_TEST, backend="jnp")(arrays)
    np.testing.assert_allclose(c(arrays)["O"], ref["O"], rtol=1e-6)


@distributed
def test_mesh_shape_tuple_and_api_facade():
    """api.jit(mesh=(2, 4)) and the api.Mesh re-export both work; the
    2-D model shape flattens to one execution axis over 8 devices."""
    import jax

    from repro import api

    assert api.Mesh is jax.sharding.Mesh
    prog = ffn()
    arrays = _arrays(prog)
    ref = api.jit(ffn(), CPU_TEST, backend="jnp")(arrays)
    c = api.jit(ffn(), CPU_TEST, backend="jnp", mesh=(2, 4))
    assert c.record.mesh["shape"] == [2, 4]
    assert c.record.mesh["n_devices"] == 8
    np.testing.assert_allclose(c(arrays)["O"], ref["O"], rtol=1e-5, atol=1e-5)

    # an explicit jax Mesh is accepted as-is
    jmesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("dev",))
    c2 = api.jit(ffn(), CPU_TEST, backend="jnp", mesh=jmesh)
    np.testing.assert_allclose(c2(arrays)["O"], ref["O"], rtol=1e-5, atol=1e-5)


@distributed
def test_mesh_compile_memory_cache_hit():
    prog = ffn()
    arrays = _arrays(prog)
    c1 = stripe_jit(ffn(), CPU_TEST, backend="jnp", mesh=8)
    c2 = stripe_jit(ffn(), CPU_TEST, backend="jnp", mesh=8)
    assert not c1.record.cache_hit
    assert c2.record.cache_hit
    assert c2.record.mesh["collective_bytes"] == \
        c1.record.mesh["collective_bytes"]
    np.testing.assert_allclose(c2(arrays)["O"], c1(arrays)["O"])


@distributed
def test_axis_size_inside_and_outside_shard_map():
    """compat.axis_size resolves inside a shard_map trace AND at trace
    level under an ambient `with mesh:` context (the satellite fix)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.parallel import compat

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("data",))

    def body(x):
        return x * compat.axis_size("data")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    out = jax.jit(fn)(jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(out), 8.0)

    # outside any trace: the ambient mesh context supplies the size
    with mesh:
        assert compat.axis_size("data") == 8
    assert compat.axis_size("data", mesh=mesh) == 8
    with pytest.raises(NameError):
        compat.axis_size("nonexistent_axis")


# --------------------------------------------------------------------------
# property tests: partitioned == single-device over drawn shapes
# --------------------------------------------------------------------------
@distributed
@settings(max_examples=6, deadline=None)
@given(m=st.sampled_from([8, 16, 24]), k=st.sampled_from([8, 16]),
       n=st.sampled_from([8, 16]))
def test_property_matmul_output_split(m, k, n):
    prog = matmul(m, k, n)
    arrays = _arrays(prog, seed=m * 100 + k * 10 + n)
    ref = stripe_jit(matmul(m, k, n), CPU_TEST, backend="jnp")(arrays)
    c = stripe_jit(matmul(m, k, n), CPU_TEST, backend="jnp", mesh=8)
    np.testing.assert_allclose(c(arrays)["O"], ref["O"],
                               rtol=1e-5, atol=1e-5)


@distributed
@settings(max_examples=4, deadline=None)
@given(m=st.sampled_from([8, 32]), k=st.sampled_from([16, 48]))
def test_property_ffn_matches(m, k):
    prog = ffn(m, k, 16)
    arrays = _arrays(prog, seed=m + k)
    ref = stripe_jit(ffn(m, k, 16), CPU_TEST, backend="jnp")(arrays)
    c = stripe_jit(ffn(m, k, 16), CPU_TEST, backend="jnp", mesh=8)
    np.testing.assert_allclose(c(arrays)["O"], ref["O"],
                               rtol=1e-5, atol=1e-5)


@distributed
@settings(max_examples=4, deadline=None)
@given(x=st.sampled_from([16, 32]), y=st.sampled_from([9, 15]),
       c=st.sampled_from([3, 5]))
def test_property_halo_conv_bit_exact(x, y, c):
    prog = halo_conv(x, y, c, 4)
    arrays = _arrays(prog, seed=x + y + c)
    ref = stripe_jit(halo_conv(x, y, c, 4), CPU_TEST, backend="jnp")(arrays)
    cc = stripe_jit(halo_conv(x, y, c, 4), CPU_TEST, backend="jnp", mesh=8)
    np.testing.assert_allclose(np.asarray(cc(arrays)["O"]),
                               np.asarray(ref["O"]), rtol=HALO_RTOL,
                               atol=HALO_ATOL)


# --------------------------------------------------------------------------
# plan-level tests (no devices)
# --------------------------------------------------------------------------
def test_plan_collective_bytes_model():
    """The plan's recorded bytes are the interconnect model's per-device
    moved bytes: all_gather (n-1)/n, psum 2(n-1)/n, halo = margin."""
    n = 8
    plan = plan_program(ffn(), n, TPU_V5E, (n,))
    ag = [c for c in plan.collectives if c.op == "all_gather"]
    assert len(ag) == 1
    assert ag[0].nbytes == pytest.approx(
        collective_seconds("all_gather", 256 * 64 * 4, n, 1.0))

    plan2 = plan_program(matmul(12, 64, 20), n, TPU_V5E, (n,))
    ps = [c for c in plan2.collectives if c.op == "psum"]
    assert len(ps) == 1
    assert ps[0].nbytes == pytest.approx(
        collective_seconds("psum", 12 * 20 * 4, n, 1.0))

    plan3 = plan_program(halo_conv(), n, TPU_V5E, (n,))
    halos = [c for c in plan3.collectives if c.op == "halo"]
    assert halos and all(h.nbytes > 0 for h in halos)


def test_plan_unsupported_raises():
    with pytest.raises(UnsupportedMesh):
        plan_program(matmul(13, 7, 5), 8, TPU_V5E, (8,))


def test_mesh_link_multiplier_lowers_comm_time():
    """A 2-D mesh shape multiplies the link bandwidth (more links per
    device) — same bytes, less exposed time."""
    flat = plan_program(ffn(), 8, TPU_V5E, (8,))
    grid = plan_program(ffn(), 8, TPU_V5E, (2, 4))
    assert grid.collective_bytes() == flat.collective_bytes()
    assert grid.comm_s < flat.comm_s


def test_partition_pass_mesh_annotation():
    """hw.with_mesh() activates the partition pass's annotation mode:
    split tags on the optimized blocks, collective records in the trace,
    comm terms in the score."""
    hw = TPU_V5E.with_mesh((8,))
    opt, rec = compile_cached(ffn(), hw)
    score = score_pass_trace(rec.pass_trace, rec.n_kernels)
    assert score.comm_bytes > 0
    assert score.n_collectives >= 1
    assert score.comm_s > 0
    tagged = [b for b in opt.entry.stmts
              if hasattr(b, "tags") and "partitioned" in b.tags]
    assert tagged, "split decision must be visible on the optimized blocks"

    base_score = score_pass_trace(
        compile_cached(ffn(), TPU_V5E)[1].pass_trace)
    assert base_score.comm_bytes == 0


def test_partition_pass_mesh_fallback_reports():
    hw = TPU_V5E.with_mesh((8,))
    opt, rec = compile_cached(matmul(13, 7, 5), hw)
    part = [e for e in rec.pass_trace if e[0] == "partition"]
    assert part and len(part[0]) > 2
    assert any("fallback" in r for r in part[0][2] if isinstance(r, dict))
    score = score_pass_trace(rec.pass_trace, rec.n_kernels)
    assert score.comm_bytes == 0


def test_with_mesh_normalizes_trivial():
    assert TPU_V5E.with_mesh((1,)).fingerprint() == TPU_V5E.fingerprint()
    assert TPU_V5E.with_mesh((1, 1)).mesh == ()
    hw = TPU_V5E.with_mesh((2, 4))
    assert hw.mesh == (2, 4)
    assert hw.mesh_devices() == 8
    assert hw.passes[0][0] == "partition"
    assert hw.fingerprint() != TPU_V5E.fingerprint()
    # idempotent: no duplicate partition pass
    again = hw.with_mesh((2, 4))
    assert [n for n, _ in again.passes].count("partition") == 1


def test_mesh_sweep_space_pareto():
    """The explore integration end-to-end without devices: the mesh axis
    sweeps, points score with comm_bytes, and the Pareto front uses the
    communication axis."""
    from repro.explore.report import PARETO_AXES, build_report, to_markdown
    from repro.explore.runner import run_sweep
    from repro.explore.space import get_space

    assert "comm_bytes" in PARETO_AXES
    space = get_space("mesh-sweep")
    assert any(a.path == "mesh" for a in space.axes)
    sweep = run_sweep(space, "default", budget=5, strategy="grid",
                      measure_top_k=0)
    report = build_report(sweep)
    meshed = [p for p in report["points"]
              if p["point"].get("mesh", (1,)) not in ((1,), [1])
              and not p["error"] and p["dedup_of"] is None]
    assert meshed, "sweep must score at least one meshed point"
    assert all(p["comm_bytes"] > 0 for p in meshed)
    # baseline (and the stock point) spend no communication
    assert report["baseline"]["comm_bytes"] == 0
    md = to_markdown(sweep)
    assert "comm (B)" in md


def test_space_mesh_axis_formatting():
    from repro.explore.space import Axis, SearchSpace

    space = SearchSpace(name="t", base="tpu_v5e",
                        axes=(Axis("mesh", ((1,), (2, 4)), default=(1,)),))
    assert space.point_name({"mesh": (2, 4)}).endswith("mesh=2x4")
    cfg = space.apply({"mesh": (2, 4)})
    assert cfg.mesh == (2, 4)
    # the stock point IS the base config (fingerprint dedupe)
    assert space.apply({"mesh": (1,)}).fingerprint() == \
        space.base_config().fingerprint()


def test_explore_help_lists_mesh_axes():
    from repro.explore.__main__ import _space_epilog

    epilog = _space_epilog()
    assert "mesh-sweep" in epilog
    assert "2x4" in epilog
