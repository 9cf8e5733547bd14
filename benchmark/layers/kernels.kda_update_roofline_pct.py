"""The decode step's delta-rule kernel's share of its roofline (%): the bytes
a call has to move (costs_kda.py: each row's state in and out, its operand
tile, its output) over the chip's peak HBM bandwidth (peaks.json), over the
kernel's measured time a call (the trace's `kda_update`: device seconds over
calls; one call is one KDA layer of one step of the decode loop). Rows a
call: the mean over the decode loop's steps of the iterations that began
inside the profiler's captures (`kda_update_rows` over `decode_steps` of the
flight recorder). None where the trace holds no such kernel or the program
records no such counter."""
import os

import costs
import costs_kda
from _kda import captured, kernel_seconds_a_call

KERNEL = "kda_update"  # ops/kda.py, as the trace prints it


def read(ctx):
    per_call_s = kernel_seconds_a_call(ctx, KERNEL)
    its = [i for i in ctx["counters"]["iterations"] if i.get("kda_update_rows")]
    if not per_call_s or not its:
        return None
    its = captured(ctx, its)
    rows = sum(i["kda_update_rows"] for i in its) / sum(i["decode_steps"] for i in its)
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    return 100.0 * (costs_kda.kda_update_call_bytes(ctx["model"], rows)
                    / peaks["hbm_bytes_per_s"]) / per_call_s
