#!/usr/bin/env python3
"""Engine phases and named kernels in a profiler trace.

The serving engine (``repro.serving.engine``) opens a span at each phase
of its loop: ``serve.admit`` (with ``serve.prep_wait`` and
``serve.prefill`` inside), ``serve.decode_step`` (with
``serve.decode.dispatch`` and ``serve.decode.sync``), ``serve.emit``, and
the ``serve.setup.*`` steps.  While ``repro.obs`` tracing is enabled each
span is also a ``jax.profiler.TraceAnnotation``, so a profile holds it on
the host thread that ran it, on the device's clock.  Each Pallas kernel
carries its block's name (``<program>.<ops>``), which the TPU compiler
gives the kernel's operation.

From one ``.xplane.pb`` this finds:

* the serving thread: the host line that holds the
  ``serve.decode.dispatch`` and ``serve.prefill`` spans;
* each device idle gap, named by the ``serve.*`` phase under which most
  of it fell, each instant going to the innermost span on the serving
  thread over it (``serving-thread: outside engine`` where no span is),
  and the idle seconds under each phase;
* the serving thread's host time between decode steps
  (:func:`step_host_gaps`);
* the kernel calls of each block name inside the decode program's runs.

A trace without ``serve.*`` spans (tracing off, or a program older than
the spans) yields no phases; the gaps are then all outside the engine.

    python3 benchmarks/chip/phases.py <trace.xplane.pb | directory>
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

import tracereduce as tr

PREFIX = "serve."
DISPATCH = "serve.decode.dispatch"
SYNC = "serve.decode.sync"
OUTSIDE = "serving-thread: outside engine"
# the compiler's suffix that keeps operation names unique
_UNIQUE = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Span:
    """One ``serve.*`` annotation on a host thread (ns, profiler clock)."""

    name: str
    start: int
    end: int
    line: str
    stats: Dict

    @property
    def step(self) -> Optional[int]:
        v = self.stats.get("step")
        return None if v is None else int(v)


def kernel_name(name: str, stats: Optional[Dict] = None) -> Optional[str]:
    """The block name of a Pallas kernel's operation event
    (``%serve_mlp_m16.mm_gate.3 = ...`` gives ``serve_mlp_m16.mm_gate``);
    None for other operations and for a kernel given no name, which the
    compiler names after the call around it (``%closed_call.59``)."""
    if not tr.is_kernel(name, stats or {}):
        return None
    base = _UNIQUE.sub("", name.partition(" = ")[0].strip().lstrip("%"))
    return base if "." in base else None


def host_spans(pd) -> List[Span]:
    """The ``serve.*`` events of every host line of a ``ProfileData``.
    A line is one thread; several can share a name (``python``), so each
    is told apart by its place in its plane: ``<plane>/<index>:<name>``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            line = f"{plane.name}/{i}:{ln.name}"
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    s = int(e.start_ns)
                    out.append(Span(e.name, s, s + int(e.duration_ns), line,
                                    dict(e.stats)))
    return out


def serving_line(spans: Sequence[Span]) -> Optional[str]:
    """The host line that holds the decode dispatches and prefills."""
    n = Counter(s.line for s in spans if s.name in (DISPATCH, "serve.prefill"))
    return n.most_common(1)[0][0] if n else None


def innermost(spans: Sequence[Span]) -> List[Tuple[int, int, str]]:
    """The time one thread's spans cover, cut into (start, end, name)
    pieces, each named by the innermost span over it.  Spans of one
    thread nest; one that outlasts its parent is cut at the parent's end."""
    segs: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []   # (end, name), innermost last
    at = 0

    def close_until(t):
        nonlocal at
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > at:
                segs.append((at, end, name))
                at = end

    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        close_until(s.start)
        if stack and s.start > at:
            segs.append((at, s.start, stack[-1][1]))
        stack.append((min(s.end, stack[-1][0]) if stack else s.end, s.name))
        at = s.start
    close_until(float("inf"))
    return segs


def idle_under(gs: Sequence[Tuple[int, int]],
               segs: Sequence[Tuple[int, int, str]]) -> List[Dict[str, int]]:
    """For each gap, the ns of it under each phase; what no span covers
    goes to ``OUTSIDE``.  ``segs`` are sorted and disjoint."""
    starts = [a for a, _, _ in segs]
    out = []
    for s, e in gs:
        by: Dict[str, int] = {}
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(segs) and segs[i][0] < e:
            a, b, name = segs[i]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                by[name] = by.get(name, 0) + ov
            i += 1
        rest = (e - s) - sum(by.values())
        if rest > 0:
            by[OUTSIDE] = rest
        out.append(by)
    return out


def gap_name(by: Dict[str, int]) -> str:
    """The phase that holds most of a gap; ``OUTSIDE`` only where no
    ``serve.*`` span overlaps it."""
    inside = {k: v for k, v in by.items() if k != OUTSIDE}
    return max(inside, key=inside.get) if inside else OUTSIDE


def step_host_gaps(spans: Sequence[Span]) -> List[float]:
    """Seconds, for each pair of consecutive decode steps on the serving
    thread, from the end of step n's ``serve.decode.sync`` to the end of
    step n+1's ``serve.decode.dispatch``: the host work (emission to the
    caller, admission, the next dispatch) that the idle device waits for."""
    line = serving_line(spans)
    ev = sorted((s for s in spans if s.line == line and s.name in (SYNC, DISPATCH)),
                key=lambda s: s.start)
    out = []
    for a, b in zip(ev, ev[1:]):
        if a.name != SYNC or b.name != DISPATCH:
            continue
        if a.step is not None and b.step is not None and b.step != a.step + 1:
            continue   # a failed step lies between them
        out.append((b.end - a.end) * 1e-9)
    return out


@dataclasses.dataclass
class Phases:
    spans: List[Span]
    line: Optional[str]
    idle_s: float
    idle_gaps: List[Tuple[str, float]]   # the longest, named
    idle_by_phase: Dict[str, float]      # seconds, the most first
    decode_runs: int
    kernel_calls: Dict[Optional[str], int]   # inside the decode runs

    def summary(self) -> Dict:
        host = step_host_gaps(self.spans)
        return {"serving_line": self.line, "idle_s": self.idle_s,
                "idle_by_phase": self.idle_by_phase,
                "idle_gaps": [[n, s] for n, s in self.idle_gaps],
                "step_host_ms": 1e3 * sum(host) / len(host) if host else None,
                "decode_runs": self.decode_runs,
                "kernel_calls": {str(k): v for k, v in sorted(
                    self.kernel_calls.items(), key=lambda kv: str(kv[0]))}}


def reduce_profile(pd) -> Phases:
    """Phases of the first chip's idle gaps, over the same span and
    operations as :func:`tracereduce.reduce_file`."""
    chips, modules = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and tr.OPS_LINE in lines:
            chips.append(tr._events(lines[tr.OPS_LINE], leaves_only=True))
            if not modules and tr.MODULES_LINE in lines:
                modules = tr._events(lines[tr.MODULES_LINE])
    if not chips or not chips[0]:
        raise RuntimeError("the trace holds no device operation")
    lo = min(e.start for ops in chips for e in ops)
    hi = max(e.end for ops in chips for e in ops)
    ops = [e for e in chips[0] if e.end > lo and e.start < hi]
    gs = tr.gaps([(max(e.start, lo), min(e.end, hi)) for e in ops], lo, hi)
    spans = host_spans(pd)
    line = serving_line(spans)
    under = idle_under(gs, innermost([s for s in spans if s.line == line]))
    by_phase: Dict[str, float] = {}
    for by in under:
        for k, v in by.items():
            by_phase[k] = by_phase.get(k, 0.0) + v * 1e-9
    named = sorted(((gap_name(by), (e - s) * 1e-9) for (s, e), by in zip(gs, under)),
                   key=lambda g: -g[1])
    runs = tr._decode_runs(modules)
    calls = Counter(kernel_name(e.name, e.stats) for e in ops
                    if tr.is_kernel(e.name, e.stats) and tr._inside(e, runs))
    return Phases(spans=spans, line=line, idle_s=sum(s for _, s in named),
                  idle_gaps=named[:10],
                  idle_by_phase=dict(sorted(by_phase.items(), key=lambda kv: -kv[1])),
                  decode_runs=len(runs), kernel_calls=dict(calls))


def reduce_file(path: str) -> Phases:
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path))


def newest_trace(path: str) -> str:
    """``path`` itself, or the newest ``.xplane.pb`` under the directory."""
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise SystemExit(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    path = newest_trace(args[0])
    print(json.dumps({"trace": path, **reduce_file(path).summary()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
