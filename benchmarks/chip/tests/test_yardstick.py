"""The yardstick against figures worked by hand, and one served block
against its own index space.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import json
import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import yardstick  # noqa: E402


def model(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


# hand-worked: per layer d(h+2kv)hd + h*hd*d + 3*d*f, times layers, plus
# the head vocab*d; attention 4*layers*heads*hd per attended position;
# KV 2*layers*kv*hd*2 bytes per position
HAND = {
    "qwen3-4b": {"layer": 15728640 + 10485760 + 74711040, "params": 4022272000,
                 "attn": 589824, "kv_row": 147456},
    "chatglm3-6b": {"layer": 18874368 + 16777216 + 168296448, "params": 5976883200,
                    "attn": 458752, "kv_row": 28672},
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_by_hand(name):
    m, h = model(name), HAND[name]
    assert yardstick.layer_matmul_params(m) == h["layer"]
    assert yardstick.matmul_params(m) == h["params"]
    assert yardstick.kv_row_bytes(m) == h["kv_row"]
    ctx = [100] * 16  # 16 live slots, each attending 100 positions
    assert yardstick.decode_flops(m, ctx) == 16 * 2 * h["params"] + h["attn"] * 1600
    assert yardstick.decode_bytes(m, 1, ctx) == 2 * h["params"] + 1600 * h["kv_row"]
    assert yardstick.decode_bytes(m, 3, []) == 3 * 2 * h["params"]


def test_peaks_and_roofline():
    pk = yardstick.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        yardstick.peaks("cpu")
    # memory bound: 819 MB at 819 GB/s is one millisecond
    assert math.isclose(yardstick.least_seconds(1e6, 819e6, pk), 1e-3)
    assert math.isclose(yardstick.least_seconds(197e12, 1.0, pk), 1.0)


def _space(block):
    return math.prod(block.idx_ranges().values())


@pytest.mark.parametrize("name", ["qkv", "attn_scores"])
def test_block_against_its_index_space(name):
    """The served block's contractions (2 x the product of each index
    space) and operands (each once, at 2 bytes) give the yardstick's
    figures, at qwen3-4b's widths with 16 slots and a 1024 window."""
    from repro import api
    from repro.core import cache as _cache
    from repro.core.hwconfig import get_config
    from repro.serving import stripe_decode as sd

    cfg, m = api.configs.get("qwen3-4b"), model("qwen3-4b")
    jc = sd.EngineLikeConfig(hw=get_config("tpu_v5e"), backend="jnp",
                             use_disk=False, cache=_cache.CompilationCache(use_disk=False))
    prog = (sd.build_qkv_program(cfg, 16, jc) if name == "qkv"
            else sd.build_scores_program(cfg, 16, 1024, jc)).program
    prog = prog.source or prog
    flops = sum(2 * _space(b) for b in prog.entry.stmts)
    nbytes = 2 * sum(math.prod(prog.buffers[n].shape)
                     for n in list(prog.inputs) + list(prog.outputs))
    want = yardstick.decode_blocks(m, 16, 1024)[name]
    assert (flops, nbytes) == (want["flops"], want["bytes"])
