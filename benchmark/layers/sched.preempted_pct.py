"""Share of the requests that arrived in the window and were preempted
(pages taken back, prompt recomputed) at least once before they finished:
the phase spine's `preemptions` (%)."""
from _common import window_phases


def read(ctx):
    n = [ph["preemptions"] for ph in window_phases(ctx) if "preemptions" in ph]
    return 100.0 * sum(1 for x in n if x > 0) / len(n) if n else None
