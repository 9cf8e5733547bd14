"""The selective-scan kernel's share of its HBM roofline (%): the bytes a
call has to move (costs_ssm.py: every token's operands, the state of each
segment) over the chip's peak HBM bandwidth (peaks.json), over the kernel's
measured time a call (the trace's `ssm_scan`: device seconds over calls; one
call is one Mamba layer of one flat step: a ragged step, or a prefill chunk
alone). Tokens and segments a call: means over the forwards that ran it in the
iterations that began inside the profiler's captures (`_ssm.captured`;
`ssm_scan_tokens`, `ssm_scan_segments`, `n_chunks` of the flight recorder; an
iteration that was not fused ran one forward a chunk). The scan is bound by
the vector and transcendental units, not by these bytes (costs_ssm.py), so the
share is small by nature. None where the trace holds no such kernel."""
import os

import costs
import costs_ssm
from _ssm import captured

KERNEL = "ssm_scan"  # ops/ssm.py, as the trace prints it


def read(ctx):
    kernels = (ctx.get("trace") or {}).get("kernels") or {}
    mine = [k for name, k in kernels.items() if KERNEL in name and k.get("calls")]
    its = [i for i in ctx["counters"]["iterations"] if i.get("ssm_scan_tokens")]
    if not mine or not its:
        return None
    its = captured(ctx, its)
    forwards = sum(1 if i.get("ragged") else max(1, i["n_chunks"]) for i in its)
    tokens = sum(i["ssm_scan_tokens"] for i in its) / forwards
    chunks = sum(i["n_chunks"] for i in its) / forwards
    rows = sum(i["ssm_scan_segments"] - i["n_chunks"] for i in its) / forwards
    per_call_s = sum(k["total_s"] for k in mine) / sum(k["calls"] for k in mine)
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    return 100.0 * (costs_ssm.ssm_scan_call_bytes(ctx["model"], tokens, rows, chunks)
                    / peaks["hbm_bytes_per_s"]) / per_call_s
