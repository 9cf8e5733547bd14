"""Median of the phase spine's prefill_s (first admission to the first
emitted token: ttft_s less queue_wait_s and kv_onboard_s) over the requests
that arrived in the window (ms)."""
from _common import window_phases


def read(ctx):
    p = [ph["prefill_s"] * 1e3 for ph in window_phases(ctx) if "prefill_s" in ph]
    return ctx["percentile"](p, 50) if p else None
