"""Weights read as stored: a program declares a matmul weight at its
stored dtype (bf16 beside f32 activations) and may be handed it in place
(``Stacked``: the stacked ``(n_layers, ...)`` array plus the layer index).

Every combination must give what the old path gave, where the caller
sliced the layer and cast it to f32 before the call: to f32 rounding on
both backends (jnp, and Pallas in interpret mode), and bit for bit
between the stacked and the plain call at one dtype.  Plus the pieces
that make it so: the memory plan prices the promoted tile, the compile
record lists the operands, and a jnp unit inside the hybrid composer
reads the slice."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import TileProgram, single_op_program, stripe_jit
from repro.core import cache as _cache
from repro.core.cost import evaluate_tiling
from repro.core.hwconfig import get_config
from repro.core.lower_jnp import Stacked
from repro.core.memplan import plan_block
from repro.serving import stripe_decode as sd

HW = get_config("tpu_v5e")
LAYERS = 3  # stacked layers handed to the programs; selected: 0 and LAYERS - 1


def _jc(backend):
    return sd.EngineLikeConfig(
        hw=HW, backend=backend, interpret=True, use_disk=False,
        cache=_cache.CompilationCache(capacity=64, use_disk=False))


def _matmul(dtype, jc):
    tp = TileProgram("stored_matmul")
    tp.input("X", (16, 256))
    tp.input("W", (256, 384), dtype)
    tp.output("O", (16, 384))
    tp.op("O[i, j] += X[i, c] * W[c, j]", name="mm")
    return stripe_jit(tp.build(), jc.hw, **sd._jit_opts(jc))


def _cfg(dtype):
    return configs.get("qwen3-4b").scaled(dtype=dtype)


PROGRAMS = {
    "contraction": _matmul,
    "qkv": lambda dtype, jc: sd.build_qkv_program(_cfg(dtype), 16, jc),
    "mlp": lambda dtype, jc: sd.build_mlp_program(_cfg(dtype), 16, jc)[0],
}


def _inputs(prog, seed=0):
    """Activations f32; each weight stacked ``(LAYERS, ...)`` at its
    declared dtype."""
    rng = np.random.default_rng(seed)
    out = {}
    for n in prog.program.inputs:
        decl = prog.program.buffers[n]
        lead = (LAYERS,) if n.startswith("W") else ()
        out[n] = jnp.asarray(rng.standard_normal(lead + decl.shape), decl.dtype)
    return out


@pytest.mark.parametrize("mode", ["stacked", "plain"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_program_reads_stored_weights(name, backend, dtype, mode):
    prog = PROGRAMS[name](dtype, _jc(backend))
    if backend == "pallas":
        assert set(prog.record.block_backends.values()) == {"pallas"}, \
            prog.record.block_fallbacks
    cast_outside = PROGRAMS[name]("float32", _jc(backend))
    arrays = _inputs(prog)
    weights = [n for n in arrays if n.startswith("W")]

    @jax.jit
    def stacked(arrays, i):
        return prog({n: Stacked(a, i) if n in weights else a for n, a in arrays.items()})

    for i in (0, LAYERS - 1):
        plain = {n: a[i] if n in weights else a for n, a in arrays.items()}
        got = prog(plain) if mode == "plain" else stacked(arrays, jnp.int32(i))
        want = cast_outside({n: a.astype(jnp.float32) for n, a in plain.items()})
        for out in prog.outputs:
            g, w = np.asarray(got[out]), np.asarray(want[out])
            assert g.dtype == np.float32
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5 * np.abs(w).max())
            if mode == "stacked":
                np.testing.assert_array_equal(g, np.asarray(prog(plain)[out]))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_compile_record_lists_stored_reads(dtype):
    """Narrow weights are listed at compile time; a weight handed in
    place is listed (and marked) once the program is called."""
    prog = sd.build_qkv_program(_cfg(dtype), 16, _jc("pallas"))
    reads = prog.record.stored_reads
    narrow = dtype == "bfloat16"
    assert sorted(reads) == (["WK", "WQ", "WV"] if narrow else [])
    arrays = _inputs(prog)
    prog({"X": arrays["X"], "WQ": Stacked(arrays["WQ"], 1),
          "WK": arrays["WK"][1], "WV": arrays["WV"][1]})
    reads = prog.record.stored_reads
    assert reads["WQ"] == {"dtype": dtype, "narrow": narrow, "in_place": True}
    assert "X" not in reads
    if narrow:
        assert reads["WK"] == {"dtype": dtype, "narrow": True, "in_place": False}
    else:
        assert "WK" not in reads


def test_hybrid_jnp_unit_reads_the_selected_slice():
    """A unit that falls back to jnp inside the Pallas composer is handed
    the slice of a stacked input; the kernels beside it read in place."""
    from repro.core.lower_pallas import lower_program_hybrid

    prog = sd.build_mlp_program(_cfg("bfloat16"), 16, _jc("pallas"))[0]
    run = lower_program_hybrid(prog.program, interpret=True,
                               force_jnp_units={"mm_gate"}, vmem_cap=None)
    assert run.block_backends["mm_gate"] == "jnp"
    arrays = _inputs(prog, seed=1)
    plain = {n: a[2] if n.startswith("W") else a for n, a in arrays.items()}
    got = run({n: Stacked(a, 2) if n.startswith("W") else a for n, a in arrays.items()})
    np.testing.assert_array_equal(np.asarray(got["Y"]), np.asarray(run(plain)["Y"]))


# --------------------------------------------------------------------------
# the promoted tile in the memory plan and the autotiler's footprint
# --------------------------------------------------------------------------
@pytest.mark.parametrize("wdtype,promoted", [("bfloat16", 128 * 128 * 4),
                                             ("float32", 0), ("int8", 0)])
def test_planner_footprint_prices_promoted_tile(wdtype, promoted):
    """A bf16 tile meeting an f32 one gets an f32 copy in the kernel body;
    same-dtype and integer operands get none."""
    adtype = "int8" if wdtype == "int8" else "float32"
    odtype = "int32" if wdtype == "int8" else "float32"
    prog = single_op_program(
        "O[i, j] += A[i, c] * B[c, j]",
        {"A": ((256, 256), adtype), "B": ((256, 256), wdtype),
         "O": ((256, 256), odtype)},
        out="O",
    )
    c = evaluate_tiling(prog.entry.stmts[0], {"i": 128, "j": 128, "c": 128}, HW,
                        {"cost": "roofline", "mem_cap_frac": 0.45})
    a_tile = 128 * 128 * np.dtype(adtype).itemsize
    b_tile = 128 * 128 * (2 if wdtype == "bfloat16" else np.dtype(wdtype).itemsize)
    o_tile = 128 * 128 * 4
    # 2xA + 2xB + O + 32-bit scratch + the promoted copy of B
    assert c.plan_bytes == 2 * a_tile + 2 * b_tile + o_tile + 128 * 128 * 4 + promoted


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_block_plan_holds_promoted_view(dtype):
    opt = PROGRAMS["contraction"](dtype, _jc("pallas")).program
    grid = [b for b in opt.entry.stmts if "grid" in getattr(b, "tags", ())]
    assert grid
    plan = plan_block(grid[0])
    promote = [a for a in plan.allocs if a.view.kind == "promote"]
    if dtype == "float32":
        assert promote == []
        return
    (view,) = [a.view for a in promote]
    w = next(a.view for a in plan.allocs if a.view.name == view.name[:-len(".promoted")])
    assert view.nbytes == 2 * w.nbytes  # the bf16 weight tile, at f32
    assert view.slots == 1
