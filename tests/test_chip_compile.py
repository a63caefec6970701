"""Compile the served qwen3-4b programs for a TPU v5e that is described,
not attached.

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
SLOTS, KV_WINDOW, PREFILL_BUCKET = 8, 1024, 128


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
    "prefill_qkv": lambda jc: sd.build_qkv_program(QWEN, PREFILL_BUCKET, jc),
    "prefill_mlp": lambda jc: sd.build_mlp_program(QWEN, PREFILL_BUCKET, jc)[0],
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_served_program_compiles_for_v5e(name, one_chip, jc):
    import jax
    import jax.numpy as jnp

    prog = PROGRAMS[name](jc)
    rec = prog.record
    assert rec.backend == "pallas", rec.fallback_reason
    assert set(rec.block_backends.values()) == {"pallas"}, rec.block_fallbacks
    shapes = {n: jax.ShapeDtypeStruct(prog.program.buffers[n].shape, jnp.float32,
                                      sharding=one_chip)
              for n in prog.program.inputs}
    compiled = jax.jit(lambda arrays: prog(arrays)).lower(shapes).compile()
    assert compiled.as_text().count("tpu_custom_call") == rec.n_kernels


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_served_kernels_carry_block_names(name, one_chip, jc):
    """Each kernel's operation in the compiled program is named after its
    block (``<program>.<member ops>``, plus the compiler's ``.<n>``), so a
    profile of the chip shows it under that name."""
    import re

    import jax
    import jax.numpy as jnp

    prog = PROGRAMS[name](jc)
    shapes = {n: jax.ShapeDtypeStruct(prog.program.buffers[n].shape, jnp.float32,
                                      sharding=one_chip)
              for n in prog.program.inputs}
    text = jax.jit(lambda arrays: prog(arrays)).lower(shapes).compile().as_text()
    ops = [re.match(r"\s*(?:ROOT )?%?(\S+) = ", line).group(1)
           for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    entry = prog.program.entry.name
    want = {f"{entry}.{unit.replace('+', '_')}" for unit in prog.record.block_backends}
    assert {re.sub(r"\.\d+$", "", op) for op in ops} == want
    assert len(ops) == prog.record.n_kernels
