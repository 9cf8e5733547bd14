"""Device time in Mosaic custom calls (the Pallas attention kernels) over
device busy time, from the trace (%)."""


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("busy_s"):
        return None
    return 100.0 * tr.get("kernel_s", 0.0) / tr["busy_s"]
