"""The bytes a median captured decode step of a decoder-hybrid-decoder has to
move (costs_sambay.py: the weights once, every row's recurrent state in and
out and its convolution inputs in nine layers, eight windows of min(context,
512) tokens a row, the full layer's live KV EIGHT times: once by the layer,
once by each cross layer) over the chip's peak HBM bandwidth (peaks.json), as
a share of the measured step (`sambay.decode_step_ms`) (%). Rows and context:
medians of the decode iterations that began inside the profiler's captures
(`_sambay.rows_and_context`)."""
import os

import costs
import costs_sambay
from _sambay import captured_decode, decode_step_ms, rows_and_context


def read(ctx):
    step = decode_step_ms(ctx)
    rows, context = rows_and_context(ctx, captured_decode(ctx))
    if not step or not rows:
        return None
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    need = costs_sambay.decode_step_bytes(ctx["model"], rows, context)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (step / 1e3)
