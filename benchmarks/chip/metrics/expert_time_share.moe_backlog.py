"""expert_time_share.moe_backlog: the device time of the grouped expert
kernels (``serve_moe_m<rows>.*``, decode and prefill) over the device's busy
time, in the traced span."""


def read(run, trace):
    if trace is None or trace.busy_s <= 0:
        return None
    spent = sum(s for name, s in trace.op_totals.items()
                if name.lstrip("%").startswith("serve_moe_m"))
    if spent <= 0:
        return None
    return 100.0 * spent / (trace.busy_s * trace.chips)
