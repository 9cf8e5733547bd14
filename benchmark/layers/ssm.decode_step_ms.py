"""Median device time of one decode step of a hybrid state-space model (ms):
a decode-loop execution's duration over the steps it ran, a step being the
`attention` kernel calls over the configuration's ATTENTION layers (2 of
ai21-jamba2-3b's 28; `runner.decode_step_ms` divides by every layer and so
finds no whole step here). None for a model without state-space layers and
where the trace holds no decode loop."""
from _ssm import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
