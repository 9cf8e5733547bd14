"""The bytes a median decode step of a hybrid state-space model has to move
(costs_ssm.py: its own weights once, every row's recurrent state in and out
and its convolution inputs in each Mamba layer, the live KV of the attention
layers) over the chip's peak HBM bandwidth (peaks.json), as a share of the
measured step (`ssm.decode_step_ms`) (%). Rows: the median `decode_seqs` of the
decode iterations that began inside the profiler's captures (`_ssm.captured`:
the step's time is theirs); live KV: their median pool usage."""
import os

import costs
import costs_ssm
from _ssm import captured, decode_step_ms


def read(ctx):
    step = decode_step_ms(ctx)
    dec = [i for i in ctx["counters"]["iterations"] if i["decode_seqs"] > 0]
    if not step or not dec:
        return None
    dec = captured(ctx, dec)
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    eng = ctx["ready"]["engine"]
    rows = ctx["percentile"]([i["decode_seqs"] for i in dec], 50)
    usage = ctx["percentile"]([i["kv_usage"] for i in dec], 50)
    need = costs_ssm.decode_step_bytes(ctx["model"], rows, usage * eng["num_pages"] * eng["page_size"])
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (step / 1e3)
