"""decode_mfu.moe_backlog: the model FLOPs of every decode token of the
window (2 x the parameters every token multiplies, the head included, 6 d f
for each routed pair, plus the attention over each token's live context;
``moe_yardstick.decode_flops``) over the window's decode time
(``serve.decode_step_s``) times the chip's bf16 peak.  Pairs a step are the
engine's ``serve.moe.decode_rows`` over ``serve.moe.decode_steps``."""
import moe_yardstick


def read(run, trace):
    c = moe_yardstick.counters()
    n, s = run.hist_delta("serve.decode_step_s")
    if c is None or not n or s <= 0:
        return None
    rows, _ = moe_yardstick.per_step(c)
    flops = moe_yardstick.decode_flops(run.model, run.decode_contexts(), rows * n)
    return 100.0 * flops / (s * run.peaks["bf16_flops_per_s"])
