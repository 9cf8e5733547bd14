"""What the idle-share readers have in common: device idle time by the host
span that owned it (the trace reduction's idle_gaps: the device trace joined
with the program's spans on the profiler's clock), over the traced window.
An owner that idle_gaps does not list counts 0."""

# a program without these spans names only step parents in idle_gaps
CHILDREN = ("engine.wait", "engine.inbox", "engine.schedule", "engine.prep",
            "engine.stage", "engine.dispatch", "engine.readback", "engine.emit",
            "engine.publish")


def idle_share_pct(ctx, owners):
    """Percent of the traced window that was idle and owned by `owners`;
    None where the program has none of the spans."""
    tr = ctx.get("trace") or {}
    gaps = dict(tr.get("idle_gaps") or [])
    if not tr.get("window_s") or not any(k in gaps for k in CHILDREN):
        return None
    return 100.0 * sum(gaps.get(o, 0.0) for o in owners) / tr["window_s"]
