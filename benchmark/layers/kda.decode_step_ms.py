"""Median device time of one decode step of a model with KDA layers (ms): a
decode-loop execution's duration over the steps it ran, a step being the
`attention` kernel calls over the configuration's MLA layers (3 of
ling-3.0-flash-vl's 18; `runner.decode_step_ms` divides by every layer and so
finds no whole step here). None for a model without KDA layers and where the
trace holds no decode loop."""
from _kda import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
