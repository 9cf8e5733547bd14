"""The attention call over the indexer's selected rows, as a share of its
roofline (%): the bytes a call has to move (costs_dsa.py: the rows' selected
latent rows as the device lays them out, the absorbed query, the output) over
the chip's peak HBM bandwidth (peaks.json), over the kernel's measured time a
call. The call is `decode_mla_attention` itself (ops/mla_attention.py) on the
buffer the selection gathered, under an identity page table: one call is one
layer of one decode step, found by that name in this cell's trace. Selected
tokens and rows a call are means over the window's decode iterations
(`dsa_sel_tokens`, `decode_seqs x decode_steps`: the flight recorder), the
time a call a mean over the captures. None where the trace holds no such
kernel or the program records no selection."""
import os

import costs
import costs_dsa

KERNEL = "decode_mla_attention"  # ops/mla_attention.py, as the trace prints it


def read(ctx):
    kernels = (ctx.get("trace") or {}).get("kernels") or {}
    mine = [k for name, k in kernels.items() if KERNEL in name and k.get("calls")]
    dec = [i for i in ctx["counters"]["iterations"]
           if i["decode_seqs"] > 0 and i.get("dsa_ctx_tokens", 0)]
    steps = sum(i["decode_steps"] for i in dec)
    if not mine or not steps or not ctx["model"].get("index_topk"):
        return None
    per_call_s = sum(k["total_s"] for k in mine) / sum(k["calls"] for k in mine)
    sel = sum(i["dsa_sel_tokens"] for i in dec) / steps
    rows = sum(i["decode_seqs"] * i["decode_steps"] for i in dec) / steps
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    need = costs_dsa.sparse_decode_call_bytes(ctx["model"], sel, rows)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / per_call_s
