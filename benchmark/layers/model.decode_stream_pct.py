"""The bytes a median decode step has to read (every weight once + the live
KV in the pool, from shapes: benchmark/costs.py) over the chip's peak HBM
bandwidth (benchmark/peaks.json), as a share of the measured step (%). Of a
routed model's experts a step has to read the ones its rows picked: the
median `moe_experts_hit` of the window's decode iterations (the flight
recorder's counter), and `n_experts_active`, the least a step can read, where
the program records none."""
import os

import costs
from _common import decode_step_ms


def read(ctx):
    step = decode_step_ms(ctx)
    dec = [i for i in ctx["counters"]["iterations"] if i["decode_seqs"] > 0]
    if not step or not dec:
        return None
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    eng = ctx["ready"]["engine"]
    usage = ctx["percentile"]([i["kv_usage"] for i in dec], 50)
    live = usage * eng["num_pages"] * eng["page_size"]
    hit = None
    if ctx["model"].get("n_experts"):
        hit = ctx["percentile"]([i.get("moe_experts_hit", 0.0) for i in dec], 50) or None
    need = (costs.weight_stream_bytes(ctx["model"], experts_hit=hit)
            + live * costs.kv_bytes_per_token(ctx["model"]))
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (step / 1e3)
