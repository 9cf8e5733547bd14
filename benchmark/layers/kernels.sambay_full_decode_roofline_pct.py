"""The decode kernel's share of its HBM roofline (%) on the ONE full cache of
a decoder-hybrid-decoder, which the full layer and every cross attention
layer read (8 calls a step on Phi-4-mini-flash, the same pages each): the
floor of a call's bytes (costs_sambay.py: the rows' live pages at 10 paired
heads of 128, the padded queries, the output) over the chip's peak HBM
bandwidth, over the measured time a call of `decode_paged_attention`. Pages a
call: the flight recorder's `decode_pages_live_global` of the captured
iterations over their decode-loop steps. `kernels.gqa_decode_roofline_pct`
reckons MiMo's global layers from a `layer_pattern` this model has none of.
None where the trace holds no such kernel or the model is another."""
from _sambay import kernel_roofline
from _swa import GLOBAL_KERNEL


def read(ctx):
    return kernel_roofline(ctx, GLOBAL_KERNEL, "decode_pages_live_global")
