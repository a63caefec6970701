"""prefill_share.backlog: share of the window spent in the engine's
batch-1 prefills, from the engine's ``serve.prefill_s`` histogram (host
time around each prefill call, which ends in a device sync)."""


def read(run, trace):
    n, s = run.hist_delta("serve.prefill_s")
    return 100.0 * s / run.seconds
