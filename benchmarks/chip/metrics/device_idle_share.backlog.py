"""device_idle_share.backlog: the share of the traced span in which no
operation ran on the device (1 - union of operation intervals / span)."""


def read(run, trace):
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
