"""ttft_p50_ms: median, over every request due in the window, of the time
from its scheduled arrival to its first token (host clock).  A request
that failed or never got a first token counts as infinitely late."""
import math

import numpy as np


def read(run, trace):
    w = run.window
    lat = [(r.times[0] - r.due if r.times and r.status in ("", "ok") else math.inf)
           for r in w.records.values() if r.due is not None and r.due < w.t_end]
    if not lat:
        return None
    v = float(np.percentile(np.asarray(lat), 50, method="higher"))
    return v * 1e3 if math.isfinite(v) else None
