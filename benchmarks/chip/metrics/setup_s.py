"""setup_s: process start to the opening of the measured window: loading,
weights, engine construction, compiling or fetching every program, and
the warm-up requests (host clock)."""


def read(run, trace):
    return run.setup_s
