"""1 - (union of device-operation intervals / traced window), mean over the
cell's chips (%)."""


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
