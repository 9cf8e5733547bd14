"""Helpers the per-layer readers share. A reader is `read(ctx) -> float or
None`; None (nothing to read) leaves the metric out of the result line.
`ctx` is built in run.py: e2e (client arithmetic), turns, w0/w1 (monotonic),
w0_wall/w1_wall, ready/counters/final (serve.py's files), trace (the trace
reduction: trace_reduce.merge's keys), routing (/debug/routing), config,
model, chips, percentile, here.
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


DECODE_LOOP_PROGRAM = "jit_decode_loop"  # model_runner._named()


def decode_loop_modules(ctx) -> list:
    """The decode-loop program's entries in the trace reduction: every
    module whose program is `jit_decode_loop`, whatever kernel its label
    names (an expert kernel called twice a layer outnumbers the attention
    kernel)."""
    mods = (ctx.get("trace") or {}).get("modules") or {}
    return [m for name, m in mods.items() if name.split("[")[0] == DECODE_LOOP_PROGRAM]


def attention_calls(m: dict) -> list:
    """Per execution of module `m`, the calls of kernels whose name holds
    `attention` (one a layer a step, GQA or latent)."""
    return [sum(n for k, n in calls.items() if "attention" in k) for calls in m["kernels"]]


def decode_step_ms(ctx):
    """Median device time of one decode step: each decode_loop execution's
    duration over the steps it ran, the steps counted as the attention
    kernel calls inside it over the layers."""
    layers = int(ctx["model"]["n_layers"])
    per = [d / (k / layers) for m in decode_loop_modules(ctx)
           for d, k in zip(m["durations_ms"], attention_calls(m))
           if k >= layers and k % layers == 0]
    if not per:
        return None
    return ctx["percentile"](per, 50)
