"""The reduction from a profiler trace to busy, idle and kernel time: on
made-up events whose answers are worked by hand, and on a short trace
recorded on a TPU v5e (a few decode steps of qwen3-4b at 16 slots).

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)

import tracereduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "data", "decode_steps.xplane.pb")


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 41)]
    assert tr.union_length(iv) == 15 + 10 + 1
    assert tr.gaps(iv, 0, 50) == [(15, 20), (30, 40), (41, 50)]
    assert tr.gaps(iv, -5, 12) == [(-5, 0)]
    assert tr.union_length([]) == 0


def _ev(name, s, e, **stats):
    return tr.Event(name, s, e, stats)


def _kernel(name):
    return (f'%{name} = f32[16,8]{{1,0}} custom-call(f32[16,4]{{1,0}} %a), '
            'custom_call_target="tpu_custom_call"')


def test_reduce_made_up_chip():
    # two runs of the decode program (jit_step(1)) and one of a prefill;
    # kernels are tpu_custom_calls; 10 ns of idle between ops, 100 ns window
    fus = "%fusion.1 = f32[16]{0} fusion(f32[16]{0} %p), kind=kLoop"
    ops = [_ev(fus, 0, 10), _ev(_kernel("closed_call.2"), 10, 30),
           _ev("%copy.3 = f32[16]{0} copy(f32[16]{0} %p)", 40, 50),
           _ev(_kernel("closed_call.7"), 50, 60),
           _ev(_kernel("closed_call.2"), 70, 80), _ev(fus, 80, 90)]
    mods = [_ev("jit_step(1)", 0, 30), _ev("jit_step(2)", 40, 60),
            _ev("jit_step(1)", 70, 90)]
    host = [_ev("PjitFunction(step)", 28, 45), _ev("wait", 58, 75)]
    r = tr.reduce_events([(ops, mods)], host, window_s=100e-9)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(70e-9)
    assert r.kernel_s == pytest.approx(40e-9)
    assert r.decode_runs == 2
    assert r.decode_kernel_s == pytest.approx(30e-9)
    assert r.decode_kernel_calls == 2
    assert r.idle_gaps == [("PjitFunction(step)", pytest.approx(10e-9)),
                           ("wait", pytest.approx(10e-9))]
    assert r.breakdown()["device_ops"][0] == ["%closed_call.2 pallas f32[16,8]",
                                              pytest.approx(30e-9)]


def test_kernel_is_named_by_its_target():
    assert tr.is_kernel(_kernel("closed_call.59"), {})
    assert not tr.is_kernel('%custom-call.8 = bf16[4]{0} custom-call(), '
                            'custom_call_target="AllocateBuffer"', {})
    assert not tr.is_kernel("%fusion.3 = f32[] fusion()", {})
    assert tr.opcode("%while.3 = (s32[], f32[2]{0:T(128)}) while((s32[]) %t), "
                     "condition=%c, body=%b") == "while"
    assert tr.opcode(_kernel("closed_call.2")) == "custom-call"


def test_recorded_chip_trace():
    """Busy time by the reduction equals a brute-force count over a 1 ns
    timeline of the same events, and every decode-step kernel call is
    found: 36 layers x 9 kernels per run of the decode program."""
    import jax

    pd = jax.profiler.ProfileData.from_file(RECORDED)
    chip = [p for p in pd.planes if p.name.startswith("/device:TPU:")][0]
    ops = [e for ln in chip.lines if ln.name == tr.OPS_LINE for e in ln.events
           if tr.opcode(e.name) not in tr.CONTAINERS]
    lo = min(e.start_ns for e in ops)
    hi = max(e.start_ns + e.duration_ns for e in ops)
    line = np.zeros(int(hi - lo) + 1, bool)
    for e in ops:
        line[int(e.start_ns - lo): int(e.start_ns + e.duration_ns - lo)] = True
    r = tr.reduce_file(RECORDED)
    assert r.busy_s == pytest.approx(line.sum() * 1e-9, rel=1e-6)
    assert 0 < r.kernel_s <= r.busy_s <= r.window_s
    assert r.decode_runs == 2
    assert r.decode_kernel_calls == r.decode_runs * 36 * 9
    assert r.kernel_s == pytest.approx(r.decode_kernel_s)
