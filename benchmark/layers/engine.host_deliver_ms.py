"""Mean step-thread time in `engine.deliver` an iteration (ms): the latency
spine's stamps, the one hand-off an event loop, the FPM and KV-event
listeners and the record's append, which run once the next program is
enqueued (flight recorder `host_deliver_s`, PR 55)."""
from _host import mean_ms


def read(ctx):
    return mean_ms(ctx, "host_deliver_s")
