"""kernel_time_share.backlog: device time of the Pallas kernels over the
device's busy time, in the traced span.  The rest is XLA's own work:
casts, the KV gather and transpose, softmax, rotary, norms."""


def read(run, trace):
    if trace is None or trace.busy_s <= 0:
        return None
    return 100.0 * trace.kernel_s / (trace.busy_s * trace.chips)
