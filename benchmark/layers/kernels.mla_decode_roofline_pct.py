"""The latent-attention decode kernel's share of its roofline (%): the bytes a
call has to move (costs_mla.py: the live latent pages of the batch as the
device lays them out, the absorbed query, the output) over the chip's peak HBM
bandwidth (peaks.json), over the kernel's measured time a call (the trace's
`decode_mla_attention`: device seconds over calls; one call is one layer of one
decode step). The kernel is bound by the page stream (costs_mla.py), so the
bytes are its roofline. Pages and rows a call are means over the window's
decode iterations (`decode_pages_live`, `decode_seqs x decode_steps`: the
flight recorder), the time a call a mean over the captures. None where the
trace holds no such kernel."""
import os

import costs
import costs_mla

KERNEL = "decode_mla_attention"  # ops/mla_attention.py, as the trace prints it


def read(ctx):
    kernels = (ctx.get("trace") or {}).get("kernels") or {}
    mine = [k for name, k in kernels.items() if KERNEL in name and k.get("calls")]
    dec = [i for i in ctx["counters"]["iterations"] if i["decode_seqs"] > 0]
    steps = sum(i["decode_steps"] for i in dec)
    if not mine or not steps or ctx["model"].get("attn_type") != "mla":
        return None
    per_call_s = sum(k["total_s"] for k in mine) / sum(k["calls"] for k in mine)
    pages = sum(i.get("decode_pages_live", 0) for i in dec) / steps
    rows = sum(i["decode_seqs"] * i["decode_steps"] for i in dec) / steps
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    need = costs_mla.decode_call_bytes(ctx["model"], pages, rows, ctx["ready"]["engine"]["page_size"])
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / per_call_s
