"""The window layers' decode kernel's share of its HBM roofline (%) on a
decoder-hybrid-decoder: the floor of a call's bytes (costs_sambay.py: the
rows' live window pages at 10 paired heads of 128, the padded queries in, the
output out) over the chip's peak HBM bandwidth (peaks.json), over the
measured time a call of `window_attention_decode` (one call is one window
layer of one step of the decode loop). Pages a call: the flight recorder's
`decode_pages_live_window` of the captured iterations over their decode-loop
steps. `kernels.window_decode_roofline_pct` reckons MiMo's two head sizes from
a `layer_pattern` this model has none of. Nine pages a row at most: the call
is bound by its grid steps, so the share is small by nature. None where the
trace holds no such kernel or the model is another."""
from _sambay import kernel_roofline
from _swa import WINDOW_KERNEL


def read(ctx):
    return kernel_roofline(ctx, WINDOW_KERNEL, "decode_pages_live_window")
