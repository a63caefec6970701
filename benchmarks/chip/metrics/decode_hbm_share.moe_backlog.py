"""decode_hbm_share.moe_backlog: the bytes the window's decode steps must
move (attention weights, router and head once a step, the weights of each
expert some token meets once a step, live KV rows read once and new rows
written once; ``moe_yardstick.decode_bytes``) over the window's decode time
times the chip's HBM bandwidth.  Experts met a step are the engine's
``serve.moe.decode_experts_hit`` over ``serve.moe.decode_steps``."""
import moe_yardstick


def read(run, trace):
    c = moe_yardstick.counters()
    n, s = run.hist_delta("serve.decode_step_s")
    if c is None or not n or s <= 0:
        return None
    _, hits = moe_yardstick.per_step(c)
    nbytes = moe_yardstick.decode_bytes(run.model, n, run.decode_contexts(), hits * n)
    return 100.0 * nbytes / (s * run.peaks["hbm_bytes_per_s"])
