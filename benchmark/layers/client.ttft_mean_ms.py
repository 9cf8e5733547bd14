"""Mean time from when a request was due to its first streamed token (ms),
over every request due in the window: the steadiest of the TTFT readings
(spread 7-8 % over a set's seeds, PERF.md section 2), still too wide for a
bound, so it stands here beside the median and the tail."""


def read(ctx):
    return ctx["e2e"].get("ttft_mean_ms")
