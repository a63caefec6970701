"""kernel_roofline.backlog: the least time the decode step's Pallas kernels
could take, over the device time they took (trace).

Each decode program whose every block lowered to Pallas is reckoned from
its own index space at the configuration's dtype
(``yardstick.decode_blocks``), once per layer per decode step, and its
roofline time is the larger of FLOPs over peak and bytes over bandwidth.
The steps counted are the kernel calls seen inside the decode program's
runs, over the calls one step makes; the time is the device time of those
calls.  A block that falls back to XLA takes its work and its time out
together."""
import yardstick


def read(run, trace):
    if trace is None or trace.decode_kernel_s <= 0:
        return None
    recs = run.decode_records
    blocks = yardstick.decode_blocks(run.model, run.engine["slots"],
                                     run.engine["max_len"])
    per_step_calls = run.model["n_layers"] * sum(r["n_kernels"] for r in recs.values())
    if not per_step_calls:
        return None
    steps = trace.decode_kernel_calls / per_step_calls
    least = sum(yardstick.least_seconds(blocks[n]["flops"], blocks[n]["bytes"], run.peaks)
                for n, r in recs.items() if r["all_pallas"] and n in blocks)
    return 100.0 * steps * run.model["n_layers"] * least / trace.decode_kernel_s
