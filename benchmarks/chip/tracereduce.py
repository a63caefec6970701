"""From a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's own (``jax.profiler``), read with
``jax.profiler.ProfileData``.  On each TPU plane (``/device:TPU:<n>``) the
``XLA Ops`` line holds one event per operation run and the ``XLA Modules``
line one event per program run.  Reduction:

* busy: the union of the operation intervals of each chip, averaged over
  the chips; idle is the traced window less busy;
* kernel time: the device time of operations that are Mosaic (Pallas)
  custom calls;
* the decode step: the program that ran most often in the window (one
  run per decode step; prefills run once per admission), with the kernel
  time inside its runs and its run count;
* breakdown: the operations that took most time, and the longest idle
  gaps, each named by the host event that covers most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TRACE_SPAN_S = 6.0


# an operation's event is named by its HLO text: "%name = type opcode(...)"
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
# operations that only hold other operations, which the trace also shows
CONTAINERS = ("while", "conditional", "call")


def opcode(name: str) -> str:
    _, _, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return m.group(1) if m else ""


def short_name(name: str) -> str:
    """The HLO name, opcode and result type of an operation's event."""
    head, _, rest = name.partition(" = ")
    kind = "pallas" if is_kernel(name, {}) else opcode(name)
    return f"{head} {kind} {rest.split('{')[0].split(' ')[0]}".strip()


def is_kernel(name: str, stats: Dict) -> bool:
    """A Mosaic (Pallas) kernel: a custom call to ``tpu_custom_call``."""
    if 'custom_call_target="tpu_custom_call"' in name:
        return True
    v = stats.get("long_name")
    return isinstance(v, str) and 'custom_call_target="tpu_custom_call"' in v


def union_length(intervals: Sequence[Tuple[int, int]]) -> int:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Event:
    name: str
    start: int   # ns
    end: int     # ns
    stats: Dict


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                   # mean over the chips
    kernel_s: float                 # Pallas kernel device time, all chips
    op_totals: Dict[str, float]     # seconds per operation name
    decode_runs: int                # runs of the decode program
    decode_kernel_s: float          # kernel time inside them
    decode_kernel_calls: int
    idle_gaps: List[Tuple[str, float]]
    chips: int

    def breakdown(self) -> Dict:
        ops = sorted(self.op_totals.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def _events(line, leaves_only: bool = False) -> List[Event]:
    out = []
    for e in line.events:
        if leaves_only and opcode(e.name) in CONTAINERS:
            continue
        st = {k: v for k, v in e.stats}
        s = int(e.start_ns)
        out.append(Event(e.name, s, s + int(e.duration_ns), st))
    return out


def reduce_file(path: str, window_s: Optional[float] = None) -> Reduced:
    """Reduce one ``.xplane.pb``.  The traced window lasted ``window_s``
    seconds on the host clock where given, else the span of the device
    operations."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    chips, host = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and OPS_LINE in lines:
            chips.append((_events(lines[OPS_LINE], leaves_only=True),
                          _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    return reduce_events(chips, host, window_s)


def reduce_events(chips, host: List[Event], window_s: Optional[float] = None) -> Reduced:
    if not chips or not any(ops for ops, _ in chips):
        raise RuntimeError("the trace holds no device operation")
    all_ops = [e for ops, _ in chips for e in ops]
    lo = min(e.start for e in all_ops)
    hi = max(e.end for e in all_ops)
    window = max(hi - lo, int((window_s or 0) * 1e9))
    busy, kernel, totals = 0, 0, {}
    decode_runs, decode_kernel, decode_calls = 0, 0, 0
    idle: List[Tuple[str, float]] = []
    for n, (ops, modules) in enumerate(chips):
        ops = [e for e in ops if e.end > lo and e.start < hi]
        iv = [(max(e.start, lo), min(e.end, hi)) for e in ops]
        busy += union_length(iv)
        for e, (s, t) in zip(ops, iv):
            key = short_name(e.name)
            totals[key] = totals.get(key, 0.0) + (t - s) * 1e-9
            if is_kernel(e.name, e.stats):
                kernel += t - s
        runs = _decode_runs(modules)
        decode_runs += len(runs)
        for e in ops:
            if is_kernel(e.name, e.stats) and _inside(e, runs):
                decode_kernel += e.end - e.start
                decode_calls += 1
        if n == 0:
            idle = _name_gaps(gaps(iv, lo, hi), host)
    return Reduced(window_s=window * 1e-9, busy_s=busy * 1e-9 / len(chips),
                   kernel_s=kernel * 1e-9, op_totals=totals,
                   decode_runs=decode_runs, decode_kernel_s=decode_kernel * 1e-9,
                   decode_kernel_calls=decode_calls, idle_gaps=idle, chips=len(chips))


def _decode_runs(modules: List[Event]) -> List[Tuple[int, int]]:
    """Runs of the program that ran most often."""
    by_name: Dict[str, List[Tuple[int, int]]] = {}
    for e in modules:
        by_name.setdefault(e.name, []).append((e.start, e.end))
    if not by_name:
        return []
    return sorted(max(by_name.values(), key=len))


def _inside(e: Event, runs: List[Tuple[int, int]]) -> bool:
    import bisect

    i = bisect.bisect_right(runs, (e.start, float("inf"))) - 1
    return i >= 0 and runs[i][0] <= e.start and e.end <= runs[i][1]


def _name_gaps(gs: List[Tuple[int, int]], host: List[Event]) -> List[Tuple[str, float]]:
    """The longest gaps, each named by the host event that overlaps it most
    (the shortest such event where several cover it alike)."""
    gs = sorted(gs, key=lambda g: g[0] - g[1])[:10]
    out = []
    for s, e in gs:
        best, best_key = "no host event", None
        for h in host:
            ov = min(e, h.end) - max(s, h.start)
            if ov <= 0:
                continue
            key = (ov, -(h.end - h.start))
            if best_key is None or key > best_key:
                best, best_key = h.name, key
        out.append((best, (e - s) * 1e-9))
    return out


class Tracer:
    """Traces a span of ``TRACE_SPAN_S`` seconds in the middle of the
    window, into ``directory`` (emptied first)."""

    def __init__(self, directory: str, seconds: float):
        self.dir = directory
        self.span = min(TRACE_SPAN_S, seconds)
        self.offset = max(0.0, (seconds - self.span) / 2)
        self.t_start = self.t_stop = None
        self.state = "idle"

    def open(self, t0: float) -> None:
        self.t_start = t0 + self.offset

    def tick(self, now: float) -> None:
        import jax

        if self.state == "idle" and self.t_start is not None and now >= self.t_start:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.t_start = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and now >= self.t_start + self.span:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self) -> Reduced:
        self.stop()
        files = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise RuntimeError(f"no trace written under {self.dir}")
        return reduce_file(files[-1], self.t_stop - self.t_start)
