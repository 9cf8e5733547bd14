"""Median time from when a turn was due to its first streamed token, over
the turns due in the window, from the client (ms). Unbounded: with 30-70
requests a window it spreads 6-20 % from run to run (PERF.md, PR 23)."""


def read(ctx):
    return ctx["e2e"].get("ttft_p50_ms")
