"""decode_mfu.backlog: the model FLOPs of every decode token of the window
(2 x matmul parameters, the head included, plus the attention over each
token's live context; ``yardstick.decode_flops``) over the window's decode
time (``serve.decode_step_s``) times the chip's bf16 peak."""
import yardstick


def read(run, trace):
    n, s = run.hist_delta("serve.decode_step_s")
    if not n or s <= 0:
        return None
    flops = yardstick.decode_flops(run.model, run.decode_contexts())
    return 100.0 * flops / (s * run.peaks["bf16_flops_per_s"])
