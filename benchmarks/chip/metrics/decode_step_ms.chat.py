"""decode_step_ms.chat: the window's decode time over its decode steps,
from the engine's ``serve.decode_step_s`` histogram."""


def read(run, trace):
    n, s = run.hist_delta("serve.decode_step_s")
    return 1e3 * s / n if n else None
