"""Compile the served qwen3-4b programs, and qwen3-moe-30b-a3b's expert
share (experts 0-15 of 128), for a TPU v5e that is described, not attached.

Each program is built by ``stripe_jit`` with compiled Pallas kernels
(``interpret=False``) at the served widths (decode: 8 slots, KV window
1024; prefill: the 128-token bucket) and compiled ahead of time by the
TPU compiler, which refuses what interpret mode accepts: blocks not
aligned to the (8, 128) tiling and kernels that ask for more VMEM than
Mosaic grants.  Nothing runs, so these tests say nothing about results
or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under several test
workers only the worker given this file may do so.
"""
import pytest

from repro.configs import get as get_arch
from repro.core import cache as _cache
from repro.core.hwconfig import get_config
from repro.serving import stripe_decode as sd

QWEN = get_arch("qwen3-4b")
SLOTS, KV_WINDOW, PREFILL_BUCKET, PAGE = 8, 1024, 128, 16
MOE = get_arch("qwen3-moe-30b-a3b-ep8")
MOE_ROWS = {"decode": 16, "prefill": 256}  # the cell's slots; its largest bucket


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def jc():
    return sd.EngineLikeConfig(
        hw=get_config("tpu_v5e"), backend="pallas", interpret=False,
        use_disk=False, cache=_cache.CompilationCache(capacity=64, use_disk=False))


PROGRAMS = {
    "decode_qkv": lambda jc: sd.build_qkv_program(QWEN, SLOTS, jc),
    "decode_attn_out": lambda jc: sd.build_attn_out_program(QWEN, SLOTS, jc),
    "decode_mlp": lambda jc: sd.build_mlp_program(QWEN, SLOTS, jc)[0],
    "decode_scores": lambda jc: sd.build_scores_program(QWEN, SLOTS, KV_WINDOW, jc),
    "decode_values": lambda jc: sd.build_values_program(QWEN, SLOTS, KV_WINDOW, jc),
    "decode_paged_scores": lambda jc: sd.build_paged_scores_program(
        QWEN, SLOTS, KV_WINDOW, PAGE, jc),
    "decode_paged_values": lambda jc: sd.build_paged_values_program(
        QWEN, SLOTS, KV_WINDOW, PAGE, jc),
    "prefill_qkv": lambda jc: sd.build_qkv_program(QWEN, PREFILL_BUCKET, jc),
    "prefill_mlp": lambda jc: sd.build_mlp_program(QWEN, PREFILL_BUCKET, jc)[0],
}


def _input_shapes(prog, sharding):
    """Each input at its declared dtype: the matmul weights at the
    configuration's (bf16), everything else f32; an input kept in pages as
    the decode step hands it, ``Paged`` over a whole 36-layer pool."""
    import jax
    import jax.numpy as jnp

    from repro.core.lower_jnp import Paged

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)  # noqa: E731
    out = {}
    for n in prog.program.inputs:
        decl = prog.program.buffers[n]
        if decl.paged:
            slots, pps = decl.shape[0], decl.shape[1] // decl.paged
            out[n] = Paged(sds((QWEN.n_layers, slots * pps + slots, decl.paged)
                               + decl.shape[2:], decl.dtype), sds((), jnp.int32),
                           table=sds((slots, pps), jnp.int32),
                           lengths=sds((slots,), jnp.int32))
        else:
            out[n] = sds(decl.shape, decl.dtype)
    return out


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_served_program_compiles_for_v5e(name, one_chip, jc):
    import jax

    prog = PROGRAMS[name](jc)
    rec = prog.record
    assert rec.backend == "pallas", rec.fallback_reason
    assert set(rec.block_backends.values()) == {"pallas"}, rec.block_fallbacks
    shapes = _input_shapes(prog, one_chip)
    compiled = jax.jit(lambda arrays: prog(arrays)).lower(shapes).compile()
    assert compiled.as_text().count("tpu_custom_call") == rec.n_kernels


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_served_kernels_carry_block_names(name, one_chip, jc):
    """Each kernel's operation in the compiled program is named after its
    block (``<program>.<member ops>``, plus the compiler's ``.<n>``), so a
    profile of the chip shows it under that name."""
    import re

    import jax

    prog = PROGRAMS[name](jc)
    shapes = _input_shapes(prog, one_chip)
    text = jax.jit(lambda arrays: prog(arrays)).lower(shapes).compile().as_text()
    ops = [re.match(r"\s*(?:ROOT )?%?(\S+) = ", line).group(1)
           for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    entry = prog.program.entry.name
    want = {f"{entry}.{unit.replace('+', '_')}" for unit in prog.record.block_backends}
    assert {re.sub(r"\.\d+$", "", op) for op in ops} == want
    assert len(ops) == prog.record.n_kernels


_HLO = {}


def _cached(fn):
    """One compile of each whole step per test process: several tests
    read the same optimized HLO."""
    def get(phase, sharding, jc):
        key = (fn.__name__, phase)
        if key not in _HLO:
            _HLO[key] = fn(phase, sharding, jc)
        return _HLO[key]
    return get


@_cached
def _step_hlo(phase, sharding, jc):
    """Optimized HLO of the whole jitted qwen3-4b decode step (8 slots,
    window 1024) or 128-bucket prefill, parameters as shapes."""
    import jax
    import jax.numpy as jnp

    from repro.models.build import build_model
    from repro.serving.paged import make_decode_step, make_prefill_step

    ps = PAGE
    pps = KV_WINDOW // ps
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)  # noqa: E731
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(build_model(QWEN).init, jax.random.PRNGKey(0)))
    pages = sds((QWEN.n_layers, SLOTS * pps + SLOTS, ps, QWEN.n_kv_heads, QWEN.hd),
                jnp.dtype(QWEN.dtype))
    i32 = jnp.int32
    if phase == "decode":
        progs = sd.build_programs(QWEN, SLOTS, jc, kv_window=KV_WINDOW, page_size=ps)
        fn = jax.jit(make_decode_step(QWEN, progs, ps), donate_argnums=(1, 2))  # as served
        args = (params, pages, pages, sds((SLOTS, pps), i32), sds((SLOTS,), i32),
                sds((SLOTS,), i32))
    else:
        progs = sd.build_programs(QWEN, PREFILL_BUCKET, jc)
        fn = jax.jit(make_prefill_step(QWEN, progs, ps, PREFILL_BUCKET))
        args = (params, sds((1, PREFILL_BUCKET), i32), sds((), i32), sds((pps,), i32),
                pages, pages)
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_served_step_reads_stored_weights_in_place(phase, one_chip, jc):
    """In the whole compiled step no weight is copied: no array of a
    weight's shape is made (no f32 convert, no per-layer dynamic-slice),
    and each matmul kernel takes the stacked bf16 ``(36, ...)`` weight
    itself, selected by the layer index in scalar prefetch."""
    import re

    text = _step_hlo(phase, one_chip, jc)
    d, h, kv, hd, f = QWEN.d_model, QWEN.n_heads, QWEN.n_kv_heads, QWEN.hd, QWEN.d_ff
    weight_shapes = {(d, h * hd), (d, kv * hd), (h * hd, d), (d, f), (f, d)}
    made = [line.strip()[:160] for line in text.splitlines()
            for m in [re.search(r"= (?:f32|bf16)\[(\d+),(\d+)\]", line)]
            if m and (int(m.group(1)), int(m.group(2))) in weight_shapes]
    assert not made, made
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r"%serve_(qkv|attn_out|mlp)_m\d+\.", line)]
    assert len(calls) == 7  # proj_q/k/v, proj_o_resid, mm_gate/up_glu/down_resid
    for line in calls:
        operands = line.split("operand_layout_constraints=", 1)[1]
        assert operands.startswith("{s32[1]"), line[:200]
        assert f"bf16[{QWEN.n_layers}," in operands.split("}}", 1)[0], line[:200]


def _moe_inputs(prog, cfg, sharding):
    """The grouped expert program's inputs as the step hands them: the
    rows f32, each expert stack whole (``layers * held`` experts, bf16)
    with one expert index and one live flag per block."""
    import jax
    import jax.numpy as jnp

    from repro.core.lower_jnp import Stacked

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)  # noqa: E731
    bufs = prog.program.buffers
    nb = bufs["X"].shape[0]
    n = cfg.n_layers * cfg.moe.held
    out = {"X": sds(bufs["X"].shape, jnp.float32)}
    for w in ("Wg", "Wu", "Wd"):
        out[w] = Stacked(sds((n,) + bufs[w].shape[1:], jnp.dtype(cfg.dtype)),
                         sds((nb,), jnp.int32), sds((nb,), jnp.int32))
    return out


@pytest.mark.parametrize("phase", sorted(MOE_ROWS))
def test_served_moe_program_compiles_for_v5e(phase, one_chip, jc):
    """``serve_moe_m<rows>``: every block Pallas, three named kernels, each
    taking the whole ``(layers * held, ...)`` bf16 expert stack with the
    per-block expert indices, their sources and live flags in scalar
    prefetch (so the block axis is outermost on every kernel's grid: live
    flags on any other axis raise)."""
    import re

    import jax

    m = MOE_ROWS[phase]
    prog = sd.build_moe_program(MOE, m, jc)
    rec = prog.record
    assert rec.backend == "pallas", rec.fallback_reason
    assert set(rec.block_backends.values()) == {"pallas"}, rec.block_fallbacks
    nb = prog.program.buffers["X"].shape[0]
    text = jax.jit(lambda arrays: prog(arrays)).lower(
        _moe_inputs(prog, MOE, one_chip)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    names = {re.sub(r"\.\d+$", "", re.match(r"\s*(?:ROOT )?%?(\S+) = ", c).group(1))
             for c in calls}
    assert names == {f"serve_moe_m{m}.{k}" for k in ("mm_gate", "mm_up_glu", "mm_down")}
    n = MOE.n_layers * MOE.moe.held
    for line in calls:
        operands = line.split("operand_layout_constraints=", 1)[1].split("}}", 1)[0]
        assert operands.startswith(f"{{s32[{nb}]{{0}}, s32[{nb}]{{0}}, s32[{nb}]{{0}}"), line[:200]
        assert f"bf16[{n}," in operands, line[:200]


@_cached
def _moe_step_hlo(phase, sharding, jc):
    """Optimized HLO of the whole jitted MoE decode step (16 slots,
    window 1024) or 256-bucket prefill, parameters as shapes."""
    import jax
    import jax.numpy as jnp

    from repro.models.build import build_model
    from repro.serving.paged import make_decode_step, make_prefill_step

    ps, m = PAGE, MOE_ROWS[phase]
    pps = KV_WINDOW // ps
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)  # noqa: E731
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(build_model(MOE).init, jax.random.PRNGKey(0)))
    pages = sds((MOE.n_layers, 16 * pps + 16, ps, MOE.n_kv_heads, MOE.hd),
                jnp.dtype(MOE.dtype))
    i32 = jnp.int32
    if phase == "decode":
        progs = sd.build_programs(MOE, m, jc, kv_window=KV_WINDOW, page_size=ps)
        fn = jax.jit(make_decode_step(MOE, progs, ps), donate_argnums=(1, 2))  # as served
        args = (params, pages, pages, sds((m, pps), i32), sds((m,), i32), sds((m,), i32))
    else:
        progs = sd.build_programs(MOE, m, jc)
        fn = jax.jit(make_prefill_step(MOE, progs, ps, m))
        args = (params, sds((1, m), i32), sds((), i32), sds((pps,), i32), pages, pages)
    assert {k: r.backend for k, r in progs.records.items()}["moe"] == "pallas"
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("phase", sorted(MOE_ROWS))
def test_moe_step_reads_expert_weights_in_place(phase, one_chip, jc):
    """In the whole compiled MoE step no expert weight is copied: an array
    of an expert matrix's shape is only ever the parameter itself or a
    view of it (``bitcast``: the ``(layers, held)`` axes merged), never a
    convert, slice, gather or copy; and each expert kernel reads the
    merged stack."""
    import re

    text = _moe_step_hlo(phase, one_chip, jc)
    d, f = MOE.d_model, MOE.moe.d_ff_expert
    shaped = re.compile(rf"= (?:f32|bf16)\[(?:\d+,)*(?:{d},{f}|{f},{d})\]\S* ([a-z-]+)\(")
    made = [line.strip()[:160] for line in text.splitlines()
            for mt in [shaped.search(line)]
            if mt and mt.group(1) not in ("parameter", "get-tuple-element", "bitcast")]
    assert not made, made
    n = MOE.n_layers * MOE.moe.held
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r"%serve_moe_m\d+\.", line)]
    assert len(calls) == 3
    for line in calls:
        assert f"bf16[{n}," in line.split("operand_layout_constraints=", 1)[1], line[:200]


@pytest.mark.parametrize("model", ["qwen3-4b", "qwen3-moe-30b-a3b"])
def test_decode_step_reads_kv_pages_in_place(model, one_chip, jc):
    """In the whole compiled decode step no KV window is made: no array of
    the window's rows, in any dtype or order (the gather, the f32 convert
    and the head-major transpose are gone), and each paged attention
    kernel takes the step's whole bf16 page pool, with the layer, the
    page table and the slots' lengths in scalar prefetch."""
    import re

    cfg, slots = (QWEN, SLOTS) if model == "qwen3-4b" else (MOE, MOE_ROWS["decode"])
    text = (_step_hlo if model == "qwen3-4b" else _moe_step_hlo)("decode", one_chip, jc)
    kv, hd, pps = cfg.n_kv_heads, cfg.hd, KV_WINDOW // PAGE
    window = {(slots * KV_WINDOW, kv, hd), (slots, KV_WINDOW, kv, hd),
              (slots, kv, KV_WINDOW, hd)}
    made = [line.strip()[:160] for line in text.splitlines()
            for mt in [re.search(r"= (?:f32|bf16)\[([\d,]+)\]", line)]
            if mt and tuple(int(n) for n in mt.group(1).split(",")) in window]
    assert not made, made
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.search(r"%serve_paged_(scores|values)_m\d+_t\d+\.", line)]
    assert len(calls) == 2
    pool = f"bf16[{cfg.n_layers},{slots * pps + slots},{PAGE},{kv},{hd}]"
    for line in calls:
        operands = line.split("operand_layout_constraints=", 1)[1]
        assert operands.startswith(f"{{s32[1]{{0}}, s32[{slots},{pps}]{{1,0}}, "
                                   f"s32[{slots}]{{0}}"), line[:200]
        assert pool in operands.split("}}", 1)[0], line[:200]


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_decode_step_writes_pools_in_place(family, one_chip, jc):
    """The served decode step, its pools donated, aliases both pool
    parameters to its outputs and makes no copy of a pool: the new rows
    are written into the pools it was handed."""
    import re

    cfg, text = ((QWEN, _step_hlo("decode", one_chip, jc)) if family == "dense"
                 else (MOE, _moe_step_hlo("decode", one_chip, jc)))
    slots = SLOTS if family == "dense" else MOE_ROWS["decode"]
    pps = KV_WINDOW // PAGE
    pool = f"bf16[{cfg.n_layers},{slots * pps + slots},{PAGE},{cfg.n_kv_heads},{cfg.hd}]"
    lines = text.splitlines()
    entry = text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    params = {int(n) for n in re.findall(
        rf"= {re.escape(pool)}\S* parameter\((\d+)\)", entry)}
    aliased = {int(n) for n in re.findall(
        r"\{\d+\}: \((\d+), \{\}", lines[0].split("input_output_alias=", 1)[-1])}
    assert len(params) == 2 and params <= aliased, (params, lines[0][:300])
    copies = [line.strip()[:160] for line in lines
              if f"= {pool}" in line and re.search(r" copy(-start)?\(", line)]
    assert not copies, copies
