"""Median device time of one decode step inside the decode_loop program,
from the trace's module line (ms)."""
from _common import decode_step_ms


def read(ctx):
    return decode_step_ms(ctx)
