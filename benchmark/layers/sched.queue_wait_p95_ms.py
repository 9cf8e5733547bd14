"""95th percentile of the scheduler's queue_wait phase (arrival at the
engine to admission) over the requests that arrived in the window (ms)."""
from _common import window_phases


def read(ctx):
    q = [p["queue_wait_s"] * 1e3 for p in window_phases(ctx) if "queue_wait_s" in p]
    return ctx["percentile"](q, 95) if q else None
