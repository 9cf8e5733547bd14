"""Helpers the per-layer readers share. A reader is `read(ctx) -> float or
None`; None (nothing to read) leaves the metric out of the result line.
`ctx` is built in run.py: e2e (client arithmetic), turns, w0/w1 (monotonic),
w0_wall/w1_wall, ready/counters/final (serve.py's files), trace (the trace
reduction), routing (/debug/routing), config, model, chips, percentile, here.
"""


def window_phases(ctx) -> list:
    """Phase spines of the requests that reached an engine inside the
    window (arrival = the wall time it finished minus its e2e_s)."""
    out = []
    for p in ctx["final"].get("phases", []):
        arrived = p["wall"] - p.get("e2e_s", 0.0)
        if ctx["w0_wall"] <= arrived < ctx["w1_wall"]:
            out.append(p)
    return out


def decode_loop_module(ctx):
    """The decode-loop program in the trace reduction: the module classed by
    the decode attention kernel (every jitted step traces as jit__unknown)."""
    tr = ctx.get("trace") or {}
    for name, m in (tr.get("modules") or {}).items():
        if "decode_paged_attention" in name:
            return m
    return None


def decode_step_ms(ctx):
    """Median device time of one decode step: each decode_loop execution's
    duration over the steps it ran, the steps counted as the attention
    kernel calls inside it over the layers."""
    m = decode_loop_module(ctx)
    if not m:
        return None
    layers = int(ctx["model"]["n_layers"])
    per = [d / (k / layers) for d, k in zip(m["durations_ms"], m["kernel_calls"])
           if k >= layers and k % layers == 0]
    if not per:
        return None
    return ctx["percentile"](per, 50)
