"""The bytes a median decode step of a model with an indexer has to read
(costs_dsa.py: every weight it uses once, the routed experts at the median
`moe_experts_hit`, the head; in every layer the rows' live pages of index keys
and their selected latent rows, as the device lays them out) over the chip's
peak HBM bandwidth (peaks.json), as a share of the measured step
(`runner.decode_step_ms`) (%). Pages and selected tokens a step: the medians
of `decode_pages_live` and `dsa_sel_tokens` over the decode steps of the
window's decode iterations. None where the program records no selection."""
import os

import costs
import costs_dsa
from _common import decode_step_ms


def read(ctx):
    step = decode_step_ms(ctx)
    dec = [i for i in ctx["counters"]["iterations"]
           if i["decode_seqs"] > 0 and i["decode_steps"] > 0 and i.get("dsa_ctx_tokens", 0)]
    if not step or not dec or not ctx["model"].get("index_topk"):
        return None
    med = lambda xs: ctx["percentile"](xs, 50)
    hit = med([i.get("moe_experts_hit", 0.0) for i in dec]) or ctx["model"]["n_experts_active"]
    need = costs_dsa.decode_step_bytes(
        ctx["model"], hit,
        med([i.get("decode_pages_live", 0) / i["decode_steps"] for i in dec]),
        med([i["dsa_sel_tokens"] / i["decode_steps"] for i in dec]),
        ctx["ready"]["engine"]["page_size"])
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (step / 1e3)
