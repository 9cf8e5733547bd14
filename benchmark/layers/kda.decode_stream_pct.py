"""The bytes a median decode step of a model with KDA layers has to move
(costs_kda.py: the weights once, with the held experts its rows reached; every
row's state in and out and its convolution inputs in each KDA layer; the live
latents of the MLA layers) over the chip's peak HBM bandwidth (peaks.json), as
a share of the measured step (`kda.decode_step_ms`) (%). Rows, experts hit and
live tokens: medians over the decode iterations that began inside the
profiler's captures (`_ssm.captured`: the step's time is theirs)."""
import os

import costs
import costs_kda
from _kda import captured, decode_step_ms


def read(ctx):
    step = decode_step_ms(ctx)
    dec = [i for i in ctx["counters"]["iterations"] if i["decode_seqs"] > 0]
    if not step or not dec:
        return None
    dec = captured(ctx, dec)
    med = lambda xs: ctx["percentile"](xs, 50)
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    eng = ctx["ready"]["engine"]
    hit = med([i.get("moe_experts_hit", 0.0) for i in dec]) or 0.0
    need = costs_kda.decode_step_bytes(
        ctx["model"], med([i["decode_seqs"] for i in dec]),
        med([i["kv_usage"] for i in dec]) * eng["num_pages"] * eng["page_size"], hit)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (step / 1e3)
