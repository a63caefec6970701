"""Engine phases in a profiler trace: idle gaps named by the serving
thread's ``serve.*`` spans, host time between decode steps and kernel
calls by block name, on made-up events whose answers are worked by hand.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import os
import sys
from types import SimpleNamespace as NS

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import phases as ph  # noqa: E402
import tracereduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "data", "decode_steps.xplane.pb")


def _ev(name, s, e, **stats):
    return NS(name=name, start_ns=s, duration_ns=e - s, stats=list(stats.items()))


def _kernel(name):
    return (f'%{name} = f32[16,8]{{1,0}} custom-call(f32[16,4]{{1,0}} %a), '
            'custom_call_target="tpu_custom_call"')


def _profile(ops, modules, host_lines):
    device = NS(name="/device:TPU:0", lines=[NS(name=tr.OPS_LINE, events=ops),
                                              NS(name=tr.MODULES_LINE, events=modules)])
    host = NS(name="/host:CPU", lines=[NS(name=n, events=evs) for n, evs in host_lines])
    return NS(planes=[device, host])


def _two_steps():
    """Two decode steps with one admission between them.  The device is
    idle over [100, 115]: emission 100-104, admission 104-110 (waiting for
    the prep thread 105-108), the next dispatch 110-118.  The prep thread
    sits in ``queue.get`` all along, covering the whole gap."""
    ops = [_ev(_kernel("serve_mlp_m16.mm_gate.3"), 5, 50),
           _ev(_kernel("serve_mlp_m16.mm_down_resid.7"), 50, 100),
           _ev(_kernel("serve_mlp_m16.mm_gate.3"), 115, 150),
           _ev(_kernel("serve_mlp_m16.mm_down_resid.7"), 150, 200)]
    mods = [_ev("jit_step(1)", 5, 100), _ev("jit_step(1)", 115, 200)]
    serving = [_ev("serve.decode_step", 0, 100, step=1),
               _ev("serve.decode.dispatch", 0, 10, step=1),
               _ev("serve.decode.sync", 10, 100, step=1),
               _ev("serve.emit", 100, 104, step=1),
               _ev("serve.admit", 104, 110),
               _ev("serve.prep_wait", 105, 108),
               _ev("serve.decode_step", 110, 200, step=2),
               _ev("serve.decode.dispatch", 110, 118, step=2),
               _ev("serve.decode.sync", 118, 200, step=2)]
    prep = [_ev("$queue.py:154 get", 0, 300), _ev("serve.prep", 300, 310, uid=4)]
    return ops, mods, [("python", serving), ("python", prep)]


def test_gap_named_by_serving_thread_not_prep_wait():
    ops, mods, host = _two_steps()
    p = ph.reduce_profile(_profile(ops, mods, host))
    assert p.line == "/host:CPU/0:python"
    assert p.idle_s == pytest.approx(15e-9)
    assert p.idle_gaps == [("serve.decode.dispatch", pytest.approx(15e-9))]
    assert p.idle_by_phase == {"serve.decode.dispatch": pytest.approx(5e-9),
                               "serve.emit": pytest.approx(4e-9),
                               "serve.admit": pytest.approx(3e-9),
                               "serve.prep_wait": pytest.approx(3e-9)}
    # the host-event rule it replaces names the gap after the prep thread
    events = [tr.Event(e.name, e.start_ns, e.start_ns + e.duration_ns, {})
              for _, evs in host for e in evs]
    assert tr._name_gaps([(100, 115)], events)[0][0] == "$queue.py:154 get"


def test_step_host_time_and_kernel_calls():
    ops, mods, host = _two_steps()
    p = ph.reduce_profile(_profile(ops, mods, host))
    # end of step 1's sync (100) to the end of step 2's dispatch (118)
    assert ph.step_host_gaps(p.spans) == [pytest.approx(18e-9)]
    assert p.summary()["step_host_ms"] == pytest.approx(18e-6)
    assert p.decode_runs == 2
    assert p.kernel_calls == {"serve_mlp_m16.mm_gate": 2,
                              "serve_mlp_m16.mm_down_resid": 2}


def test_failed_step_is_not_paired():
    spans = [ph.Span("serve.decode.sync", 0, 10, "a", {"step": 4}),
             ph.Span("serve.decode.dispatch", 12, 15, "a", {"step": 4}),
             ph.Span("serve.decode.sync", 15, 30, "a", {"step": 4}),
             ph.Span("serve.decode.dispatch", 33, 37, "a", {"step": 5})]
    assert ph.step_host_gaps(spans) == [pytest.approx(7e-9)]


def test_without_spans_every_gap_is_outside():
    ops, mods, _ = _two_steps()
    p = ph.reduce_profile(_profile(ops, mods, [("python", [_ev("$builtins next", 0, 300)])]))
    assert p.line is None and p.spans == []
    assert p.idle_gaps == [(ph.OUTSIDE, pytest.approx(15e-9))]
    assert p.idle_by_phase == {ph.OUTSIDE: pytest.approx(15e-9)}
    assert p.summary()["step_host_ms"] is None


def test_innermost_cuts_by_hand():
    spans = [ph.Span("a", 0, 10, "x", {}), ph.Span("b", 2, 4, "x", {}),
             ph.Span("c", 6, 14, "x", {}),   # outlasts its parent: cut at 10
             ph.Span("d", 20, 25, "x", {})]
    assert ph.innermost(spans) == [(0, 2, "a"), (2, 4, "b"), (4, 6, "a"),
                                   (6, 10, "c"), (20, 25, "d")]
    by = ph.idle_under([(3, 7), (12, 22)], ph.innermost(spans))
    assert by == [{"b": 1, "a": 2, "c": 1}, {ph.OUTSIDE: 8, "d": 2}]
    assert [ph.gap_name(b) for b in by] == ["a", "d"]


def test_kernel_name_by_hand():
    assert ph.kernel_name(_kernel("serve_mlp_m16.mm_gate.3")) == "serve_mlp_m16.mm_gate"
    assert ph.kernel_name(_kernel("serve_qkv_m1.proj_q_p1.12")) == "serve_qkv_m1.proj_q_p1"
    assert ph.kernel_name(_kernel("closed_call.59")) is None   # given no name
    assert ph.kernel_name("%fusion.3 = f32[] fusion()") is None


def test_recorded_trace_without_spans():
    """A chip trace taken before the engine's spans and kernel names (two
    decode steps of qwen3-4b): every gap is outside the engine, every
    kernel call is anonymous, and idle time and decode runs agree with
    the device reduction."""
    p = ph.reduce_file(RECORDED)
    r = tr.reduce_file(RECORDED)
    assert p.spans == [] and set(p.idle_by_phase) == {ph.OUTSIDE}
    assert p.idle_s == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert p.decode_runs == r.decode_runs == 2
    assert p.kernel_calls == {None: r.decode_kernel_calls}
