"""expert_roofline.moe_backlog: the least time of the grouped expert kernels
of the decode step (``moe_yardstick.expert_block`` from the pairs and the
experts met a step, the larger of FLOPs over peak and bytes over
bandwidth), times the decode program's runs in the trace, over the device
time of the decode step's expert kernels (``serve_moe_m<slots>.*``)."""
import moe_yardstick
import yardstick


def read(run, trace):
    c = moe_yardstick.counters()
    if c is None or trace is None or not trace.decode_runs:
        return None
    prefix = f"serve_moe_m{run.engine['slots']}."
    spent = sum(s for name, s in trace.op_totals.items()
                if name.lstrip("%").startswith(prefix))
    if spent <= 0:
        return None
    rows, hits = moe_yardstick.per_step(c)
    blk = moe_yardstick.expert_block(run.model, rows, hits)
    least = yardstick.least_seconds(blk["flops"], blk["bytes"], run.peaks)
    return 100.0 * least * trace.decode_runs / spent
