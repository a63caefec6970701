"""tokens_per_s: every output token the stream handed over inside the
window, over the window's seconds (host clock)."""


def read(run, trace):
    w = run.window
    n = sum(1 for r in w.records.values() for t in r.times if w.t0 <= t <= w.t_end)
    return n / run.seconds
