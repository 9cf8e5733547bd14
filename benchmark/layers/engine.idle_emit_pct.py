"""Device idle time whose midpoint lay after a step's results were ready: the
spans engine.readback (the device had finished and the host had not yet
asked), engine.emit (stop checks, handing tokens to asyncio) and
engine.publish (events and records), over the traced window (%)."""
from _idle import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx, ("engine.readback", "engine.emit", "engine.publish"))
