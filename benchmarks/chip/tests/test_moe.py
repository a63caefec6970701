"""The routed-expert cell on the CPU, at test widths: the harness reads a
sound MoE stand-in as correct, and a step that drops a held expert or
skips the top-k renormalisation, or the fp8 control, as not; the expert
yardstick against figures worked by hand; and the four MoE metric readers
on a made-up run and trace.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import json
import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import harness  # noqa: E402
import moe_yardstick  # noqa: E402
import serve  # noqa: E402
import tracereduce  # noqa: E402
import yardstick  # noqa: E402

KIND = "TPU v5 lite"
CELL = "tiny_moe.decode_backlog"
MOE_CELL = "qwen3-moe-30b-a3b.decode_backlog"


def moe_bench():
    """BENCHMARK.json with a cell of the test-width MoE stand-in added,
    reporting what the MoE cell reports."""
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    bench["configs"].append({"name": "tiny_moe",
                             "file": "benchmarks/chip/tests/data/tiny_moe.json"})
    bench["workloads"].append({"name": CELL, "config": "tiny_moe",
                               "traffic": "../tests/data/tiny_backlog", "chips": 1})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if MOE_CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    return bench


def test_sound_moe_run_is_correct():
    out = harness.run_cell(CELL, 2**33 + 7, 2.0, False, require_tpu=False,
                           bench=moe_bench(), kind=KIND)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert {"tokens_per_s", "setup_s"} <= set(out["metrics"])
    json.dumps(out, allow_nan=False)


def _broken_route(monkeypatch, how):
    """Break the served routing where the steps call it."""
    import jax
    import jax.numpy as jnp

    from repro.serving import paged

    real = paged._route

    def route(cfg, x2, router, valid):
        gate, expert = real(cfg, x2, router, valid)
        if how == "drop_expert":    # held expert 0 computes nothing
            return gate, jnp.where(expert == 0, cfg.moe.held, expert)
        # the picks' probabilities as they are, not renormalised
        logits = jnp.einsum("md,de->me", x2, router.astype(jnp.float32),
                            precision=paged._HI)
        probs, _ = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.moe.top_k)
        return probs, expert

    monkeypatch.setattr(paged, "_route", route)


@pytest.mark.parametrize("how", ["drop_expert", "no_renormalise"])
def test_fault_makes_moe_run_incorrect(monkeypatch, how):
    _broken_route(monkeypatch, how)
    out = harness.run_cell(CELL, 5, 2.0, False, require_tpu=False,
                           bench=moe_bench(), kind=KIND)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_limit(seed):
    """Through the harness's own check, the program reads below the limit
    and the fp8 control above it."""
    cell, conf = harness.find_cell(moe_bench(), CELL)
    s = harness.Setup(cell, conf, seed, require_tpu=False, kind=KIND)
    w = serve.run_window(s.api, s.engine, s.params, s.mix, seed, 2.0,
                         s.model["vocab"], lambda: 0)
    s.free_engine()
    ok, nums, ctrl = harness.check_served(s, w, seed, control="fp8")
    assert ok, nums
    assert ctrl > s.spec["correct"]["max_logit_gap"]


def model(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def test_expert_counts_by_hand():
    """qwen3-moe-30b-a3b: per layer 2048 x (32 + 8) x 128 + 4096 x 2048
    attention, 2048 x 128 router; head 151936 x 2048; an expert 3 x 2048 x
    768; a KV row 2 x 48 x 4 x 128 x 2 bytes."""
    m = model("qwen3-moe-30b-a3b")
    layer = 2048 * 40 * 128 + 4096 * 2048 + 2048 * 128
    assert moe_yardstick.token_params(m) == 48 * layer + 151936 * 2048
    assert moe_yardstick.expert_params(m) == 4718592
    assert yardstick.kv_row_bytes(m) == 98304
    ctx = [100] * 16
    tp, ep = moe_yardstick.token_params(m), 4718592
    attn = 4 * 48 * 32 * 128
    assert moe_yardstick.decode_flops(m, ctx, 700) == 16 * 2 * tp + 2 * ep * 700 + attn * 1600
    assert moe_yardstick.decode_bytes(m, 1, ctx, 500) == 2 * (tp + 500 * ep) + 1600 * 98304
    blk = moe_yardstick.expert_block(m, 768, 494)
    assert blk == {"flops": 2 * ep * 768, "bytes": 2 * (494 * ep + 2 * 768 * 2048)}
    # 10.3 of 16 experts hit a layer (16 tokens x 8 picks) move 4.67 GB a step
    assert 2 * 10.3 * 48 * ep == pytest.approx(4.67e9, rel=0.01)


def _counters(monkeypatch, steps, rows, hits):
    monkeypatch.setattr(moe_yardstick, "counters", lambda: {
        "decode_steps": steps, "decode_rows": rows, "decode_experts_hit": hits})


def _run(m, steps, step_s, ctx):
    """A made-up run: ``steps`` decode steps of ``step_s`` each in the
    window, 16 tokens each at context ``ctx``."""
    pk = yardstick.peaks(KIND)
    return types.SimpleNamespace(
        model=m, peaks=pk, engine={"slots": 16, "max_len": 1024, "page_size": 16},
        hist_delta=lambda name: (steps, steps * step_s),
        decode_contexts=lambda: [ctx] * (16 * steps))


def _trace(op_totals, decode_runs, busy_s=1.0):
    return tracereduce.Reduced(
        window_s=2.0, busy_s=busy_s, kernel_s=sum(op_totals.values()),
        op_totals=op_totals, decode_runs=decode_runs, decode_kernel_s=0.0,
        decode_kernel_calls=0, idle_gaps=[], chips=1)


def _read(name, run, trace):
    mod = harness.load_module(os.path.join(CHIP, "metrics", name + ".py"), "m_" + name)
    return mod.read(run, trace)


def test_expert_roofline_reads_100_at_its_roofline(monkeypatch):
    """Expert kernels that take exactly the yardstick's least time read
    100%, at twice that 50%; prefill kernels (another row count) and other
    operations do not count."""
    m = model("qwen3-moe-30b-a3b")
    _counters(monkeypatch, 100, 100 * 768, 100 * 494)
    blk = moe_yardstick.expert_block(m, 768, 494)
    least = yardstick.least_seconds(blk["flops"], blk["bytes"], yardstick.peaks(KIND))
    runs = 10
    ops = {"%serve_moe_m16.mm_gate.3 pallas f32[32,8,768]": least * runs / 3,
           "%serve_moe_m16.mm_up_glu.3 pallas f32[32,8,768]": least * runs / 3,
           "%serve_moe_m16.mm_down.3 pallas f32[32,8,2048]": least * runs / 3,
           "%serve_moe_m128.mm_gate.5 pallas f32[80,8,768]": 1.0,
           "%fusion.2 fusion f32[16]": 1.0}
    run = _run(m, 10, 0.04, 300)
    assert _read("expert_roofline.moe_backlog", run, _trace(ops, runs)) == pytest.approx(100.0)
    slow = {k: 2 * v if "m16" in k else v for k, v in ops.items()}
    assert _read("expert_roofline.moe_backlog", run, _trace(slow, runs)) == pytest.approx(50.0)
    # every serve_moe kernel, prefill's too, over busy time
    share = _read("expert_time_share.moe_backlog", run, _trace(ops, runs, busy_s=4.0))
    assert share == pytest.approx(100.0 * (least * runs + 1.0) / 4.0)


def test_decode_shares_by_hand(monkeypatch):
    m = model("qwen3-moe-30b-a3b")
    _counters(monkeypatch, 50, 50 * 768, 50 * 494)
    run = _run(m, 10, 0.04, 300)
    pk = yardstick.peaks(KIND)
    ctx = [300] * 160
    flops = moe_yardstick.decode_flops(m, ctx, 768 * 10)
    nbytes = moe_yardstick.decode_bytes(m, 10, ctx, 494 * 10)
    assert _read("decode_mfu.moe_backlog", run, None) == pytest.approx(
        100 * flops / (0.4 * pk["bf16_flops_per_s"]))
    assert _read("decode_hbm_share.moe_backlog", run, None) == pytest.approx(
        100 * nbytes / (0.4 * pk["hbm_bytes_per_s"]))
    # bytes a step over the step time: about 8 GB in 40 ms is ~25% of 819 GB/s
    assert 20 < _read("decode_hbm_share.moe_backlog", run, None) < 30


@pytest.mark.parametrize("name", ["decode_mfu.moe_backlog", "decode_hbm_share.moe_backlog",
                                  "expert_roofline.moe_backlog",
                                  "expert_time_share.moe_backlog"])
def test_readers_find_nothing_without_counters_or_kernels(monkeypatch, name):
    """A program without the expert counters or kernels (the parent's)
    gives no reading, and no reader raises."""
    monkeypatch.setattr(moe_yardstick, "counters", lambda: None)
    run = _run(model("qwen3-moe-30b-a3b"), 10, 0.04, 300)
    assert _read(name, run, _trace({"%fusion.2 fusion f32[16]": 1.0}, 10)) is None
