"""Mean step-thread time in `engine.emit` an iteration, exposed and hidden
together (ms)."""
from _host import mean_ms


def read(ctx):
    return mean_ms(ctx, "host_emit_s")
