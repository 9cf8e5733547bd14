"""Device idle time that no span explains, over the traced window (%): gaps
owned by `unattributed` (outside the step thread's loop) or by a bare step
parent (a hole between its children). engine.wait is owned: waiting for a
request counts in none of the idle shares. The three idle shares, this and
engine.wait's add up to device.idle_pct, less what idle_gaps' cut to ten
owners dropped. Near zero when the spans tile the iteration."""

PARENTS = ("engine.decode", "engine.mixed", "engine.prefill",
           "engine.prefill_packed", "engine.spec_verify")


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("window_s") or tr.get("idle_gaps") is None:
        return None
    gaps = dict(tr["idle_gaps"])
    return 100.0 * sum(gaps.get(o, 0.0) for o in PARENTS + ("unattributed",)) / tr["window_s"]
