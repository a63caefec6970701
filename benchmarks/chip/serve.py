"""Drive the public serving entry (``ServingEngine.submit`` and
``generate``) with a traffic mix for a fixed window, timing every token
on the host clock as the stream hands it over.

Everything here is the benchmark's own: the engine is only submitted to
and iterated.  The one loop runs on one thread; arrivals that fall due
while a step runs are submitted as soon as the stream yields.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import traffic


@dataclasses.dataclass
class Record:
    """What one request went through, on ``time.perf_counter``'s clock."""

    uid: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float]          # absolute scheduled arrival (open loop)
    submit: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = ""              # the engine's terminal status, once done


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    records: Dict[int, Record]
    hist0: Dict[str, tuple]       # engine histograms (count, sum) at t0
    hist1: Dict[str, tuple]       # ... and at t_end
    late_s: List[float]           # submit - due, open loop
    compiles: int                 # JAX compile/trace events in [t0, t_end]


HISTS = ("serve.prefill_s", "serve.decode_step_s")


def _hists(engine) -> Dict[str, tuple]:
    reg = engine.metrics_registry()
    return {n: (reg.histogram(n).count, reg.histogram(n).sum) for n in HISTS}


def run_window(api, engine, params, mix: dict, seed: int, seconds: float,
               vocab: int, compile_count: Callable[[], int],
               on_open: Optional[Callable[[float], None]] = None,
               on_tick: Optional[Callable[[float], None]] = None,
               first_uid: int = 0, drain_s: float = 60.0) -> Window:
    """Serve ``mix`` for ``seconds``.  A backlog window stops at its end;
    an open-loop window then goes on serving, with no new arrivals, until
    every request due in the window has its first token (at most
    ``drain_s`` more), so that a late first token counts as late."""
    plan = traffic.requests(mix, seed, vocab, seconds)
    backlog = mix["loop"] == "backlog"
    want_queued = int(mix.get("backlog", 0))
    slots = engine.config.slots
    recs: Dict[int, Record] = {}
    reqs: Dict[int, object] = {}
    late: List[float] = []
    started = 0
    nxt = next(plan)

    def submit(p, now):
        uid = first_uid + p.index
        r = api.Request(uid=uid, prompt=p.prompt,
                        sampling=api.SamplingParams(max_new_tokens=p.max_new))
        rec = Record(uid, p.prompt, p.max_new, None if p.due is None else t0 + p.due)
        rec.submit = now
        if rec.due is not None:
            late.append(now - rec.due)
        recs[uid], reqs[uid] = rec, r
        if not engine.submit(r):
            rec.status = "shed"

    c0 = compile_count()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    hist0 = _hists(engine)
    if on_open is not None:
        on_open(t0)
    if backlog:
        for _ in range(slots + want_queued):
            submit(nxt, t0)
            nxt = next(plan)
    it = None
    hist1 = None
    compiles = None
    while True:
        now = time.perf_counter()
        if hist1 is None and now >= t_end:
            hist1 = _hists(engine)
            compiles = compile_count() - c0
            if backlog:
                break
        if on_tick is not None:
            on_tick(now)
        if hist1 is None:
            if backlog:
                while len(recs) - started < want_queued:
                    submit(nxt, now)
                    nxt = next(plan)
            else:
                while t0 + nxt.due <= now and t0 + nxt.due < t_end:
                    submit(nxt, now)
                    nxt = next(plan)
        else:
            waiting = [r for r in recs.values() if not r.times and not r.status]
            if not waiting or now > t_end + drain_s:
                break
        if it is None:
            if all(reqs[u].done for u in reqs):
                if hist1 is None and not backlog:
                    time.sleep(max(0.0, min(t0 + nxt.due, t_end) - now))
                continue
            it = engine.generate([], params=params, max_steps=1 << 40)
        try:
            uid, tok = next(it)
        except StopIteration:
            it = None
            continue
        t = time.perf_counter()
        rec = recs[uid]
        if not rec.times:
            started += 1
        rec.times.append(t)
        rec.tokens.append(int(tok))
    if it is not None:
        it.close()
    for uid, r in reqs.items():
        if r.done and not recs[uid].status:
            recs[uid].status = r.status
    return Window(t0, t_end, recs, hist0, hist1, late, compiles)


def warm_up(api, engine, params, buckets, vocab: int, first_uid: int) -> None:
    """Compile every program the window will use: the decode step and one
    prefill per prompt bucket, by serving one short request per bucket."""
    rng = np.random.default_rng(0)
    for i, b in enumerate(buckets):
        engine.submit(api.Request(
            uid=first_uid + i, prompt=rng.integers(0, vocab, b, dtype=np.int32),
            sampling=api.SamplingParams(max_new_tokens=2)))
    engine.run(params, max_steps=1 << 20)
