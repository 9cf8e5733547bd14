"""Device idle time whose midpoint lay in getting a step to the device: the
spans engine.prep (per-sequence lists, masks, sampling params), engine.stage
(padding, host-to-device transfers) and engine.dispatch (the jitted call: an
enqueue, or a compile), over the traced window (%)."""
from _idle import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx, ("engine.prep", "engine.stage", "engine.dispatch"))
