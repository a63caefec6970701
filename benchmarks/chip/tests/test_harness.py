"""The harness on the CPU, at test widths: it refuses machines it cannot
measure, and its check of the served tokens fails for each fault a
serving cell can have and for the fp8 control.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import harness  # noqa: E402
import serve  # noqa: E402

KIND = "TPU v5 lite"


def tiny_bench():
    """BENCHMARK.json with two cells of the test-width stand-in added."""
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    bench["configs"].append({"name": "tiny", "file": "benchmarks/chip/tests/data/tiny.json"})
    bench["workloads"] += [
        {"name": "tiny.decode_backlog", "config": "tiny",
         "traffic": "../tests/data/tiny_backlog", "chips": 1},
        {"name": "tiny.short_chat", "config": "tiny",
         "traffic": "../tests/data/tiny_chat", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                "tiny." + w.split(".", 1)[1] for w in m["workloads"]]
    return bench


def test_main_refuses_cpu(capsys):
    rc = harness.main(["--workload", "qwen3-4b.decode_backlog", "--seed", "1",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2
    assert "no TPU" in out.err
    assert not out.out.strip().endswith("}")


class _Dev:
    platform, device_kind = "tpu", "TPU v99 imaginary"


class _Jax:
    @staticmethod
    def devices():
        return [_Dev()]


def test_unknown_device_kind_refused():
    with pytest.raises(harness.NoChip, match="no peaks"):
        harness.check_device(_Jax, 1)


def test_too_few_chips_refused():
    _Dev.device_kind = KIND
    try:
        with pytest.raises(harness.NoChip, match="needs 4 chips"):
            harness.check_device(_Jax, 4)
    finally:
        _Dev.device_kind = "TPU v99 imaginary"


@pytest.mark.parametrize("cell", ["tiny.decode_backlog", "tiny.short_chat"])
def test_sound_run_is_correct(cell):
    out = harness.run_cell(cell, 2**33 + 7, 2.0, False, require_tpu=False,
                           bench=tiny_bench(), kind=KIND)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    names = set(out["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert list(out)[-1] == "checks"
    json.dumps(out, allow_nan=False)


def _broken_step(monkeypatch, how):
    """Break the decode step where it is built, under the engine."""
    from repro.serving import engine as eng

    real = eng.make_decode_step

    def make(cfg, progs, page_size):
        step = real(cfg, progs, page_size)

        def broken(params, pages_k, pages_v, page_table, pos, tok):
            nxt, pk, pv = step(params, pages_k, pages_v, page_table, pos, tok)
            if how == "token":      # a token altered where it is produced
                return (nxt + 1) % cfg.vocab, pk, pv
            return nxt, pages_k, pages_v   # the state returned unchanged

        return broken

    monkeypatch.setattr(eng, "make_decode_step", make)


@pytest.mark.parametrize("how", ["token", "state"])
def test_fault_makes_run_incorrect(monkeypatch, how):
    _broken_step(monkeypatch, how)
    out = harness.run_cell("tiny.decode_backlog", 5, 2.0, False, require_tpu=False,
                           bench=tiny_bench(), kind=KIND)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_fails_the_limit(seed):
    """The control, the reference at fp8 in the program's place, reads
    above the limit while the program reads below it."""
    bench = tiny_bench()
    cell, conf = harness.find_cell(bench, "tiny.decode_backlog")
    s = harness.Setup(cell, conf, seed, require_tpu=False, kind=KIND)
    w = serve.run_window(s.api, s.engine, s.params, s.mix, seed, 2.0,
                         s.model["vocab"], lambda: 0)
    s.free_engine()
    ok, nums, ctrl = harness.check_served(s, w, seed, control="fp8")
    assert ok, nums
    assert ctrl > s.spec["correct"]["max_logit_gap"]
