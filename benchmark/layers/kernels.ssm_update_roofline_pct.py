"""The decode step's state-update kernel's share of its roofline (%): the
bytes a call has to move (costs_ssm.py: each row's state in and out, its
operands, A once) over the chip's peak HBM bandwidth (peaks.json), over the
kernel's measured time a call (the trace's `ssm_update`: device seconds over
calls; one call is one Mamba layer of one step of the decode loop). Rows a
call: the mean over the decode loop's steps of the iterations that began
inside the profiler's captures (`_ssm.captured`; a ragged iteration's first
step runs in the ragged program, on `ssm_scan`). None where the trace holds
no such kernel."""
import os

import costs
import costs_ssm
from _ssm import captured

KERNEL = "ssm_update"  # ops/ssm.py, as the trace prints it


def read(ctx):
    kernels = (ctx.get("trace") or {}).get("kernels") or {}
    mine = [k for name, k in kernels.items() if KERNEL in name and k.get("calls")]
    steps = [(i["decode_seqs"], i["decode_steps"] - (1 if i.get("ragged") else 0))
             for i in captured(ctx, [i for i in ctx["counters"]["iterations"]
                                     if i["decode_seqs"] > 0])]
    n_steps = sum(n for _, n in steps)
    if not mine or not n_steps or not ctx["model"].get("mamba_d_state"):
        return None
    per_call_s = sum(k["total_s"] for k in mine) / sum(k["calls"] for k in mine)
    rows = sum(r * n for r, n in steps) / n_steps
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    return 100.0 * (costs_ssm.ssm_update_call_bytes(ctx["model"], rows)
                    / peaks["hbm_bytes_per_s"]) / per_call_s
