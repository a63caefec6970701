"""decode_hbm_share.backlog: the bytes the window's decode steps must move
(every matmul weight once a step, live KV rows read once and new rows
written once, at the configuration's dtype; ``yardstick.decode_bytes``)
over the window's decode time times the chip's HBM bandwidth."""
import yardstick


def read(run, trace):
    n, s = run.hist_delta("serve.decode_step_s")
    if not n or s <= 0:
        return None
    nbytes = yardstick.decode_bytes(run.model, n, run.decode_contexts())
    return 100.0 * nbytes / (s * run.peaks["hbm_bytes_per_s"])
