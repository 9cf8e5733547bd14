"""Mean of the phase spine's `drain_wait_s` over ALL the requests that arrived
in the window (ms): what a prompt's chunks waited, inside `prefill_s`, for the
commit of the decode dispatch in flight before their own dispatch could be
enqueued (0.0 for a request that waited for none, which is why it sets beside
`client.ttft_mean_ms`). None on a program whose spine lacks the key."""
from _common import window_phases


def read(ctx):
    p = window_phases(ctx)
    if not p or any("drain_wait_s" not in ph for ph in p):
        return None
    return 1e3 * sum(ph["drain_wait_s"] for ph in p) / len(p)
