"""itl_p95_ms: 95th percentile of every gap between consecutive tokens of
one request, both tokens inside the window (host clock)."""
import numpy as np


def read(run, trace):
    w = run.window
    gaps = [b - a for r in w.records.values()
            for a, b in zip(r.times, r.times[1:]) if w.t0 <= a and b <= w.t_end]
    if not gaps:
        return None
    return float(np.percentile(np.asarray(gaps), 95)) * 1e3
