"""95th percentile of the same, from the client (ms); the sample count is on
an earlier line of the run. Unbounded: a tail of 30-70 requests swings 20-50 %."""


def read(ctx):
    return ctx["e2e"].get("ttft_p95_ms")
