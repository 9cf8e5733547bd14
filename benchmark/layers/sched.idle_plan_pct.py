"""Device idle time whose midpoint lay in the host's scheduling: the spans
engine.schedule (scheduler.step_plan: admission, page allocation, preemption)
and engine.inbox (new requests, exports, drafts), over the traced window (%)."""
from _idle import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx, ("engine.schedule", "engine.inbox"))
