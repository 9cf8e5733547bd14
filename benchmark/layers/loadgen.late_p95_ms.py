"""How late the generator sent, 95th percentile over the turns due in the
window (ms): a starved generator must not read as a fast server."""


def read(ctx):
    return ctx["e2e"].get("late_p95_ms")
