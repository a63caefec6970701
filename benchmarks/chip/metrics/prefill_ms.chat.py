"""prefill_ms.chat: mean time of one admission's prefill in the window,
from the engine's ``serve.prefill_s`` histogram."""


def read(run, trace):
    n, s = run.hist_delta("serve.prefill_s")
    return 1e3 * s / n if n else None
