"""Mean step-thread time in `engine.stage` an iteration, exposed and hidden
together (ms): the CPU time of staging, which is what a host-bound run-ahead
iteration pays whether a program is queued or not."""
from _host import mean_ms


def read(ctx):
    return mean_ms(ctx, "host_stage_s")
