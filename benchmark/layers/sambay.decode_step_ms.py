"""Median device time of one decode step of a decoder-hybrid-decoder (ms): a
decode-loop execution's duration over the steps it ran, a step being 16
`attention` kernel calls (8 window layers, the full layer, 7 cross layers of
Phi-4-mini-flash; or 9 `ssm_update` calls where no attention kernel is in the
trace): `_sambay.decode_step_ms`. `runner.decode_step_ms` divides by every
layer and `ssm.decode_step_ms` by Jamba's period, so neither finds a whole
step here. None for another model and where the trace holds no decode loop."""
from _sambay import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
