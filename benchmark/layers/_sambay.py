"""What the readers of a decoder-hybrid-decoder's decode step share
(dynamo_tpu/models/sambay.py; costs_sambay.py)."""
import costs_sambay
from _common import attention_calls, decode_loop_modules
from _ssm import captured
from _swa import call_seconds, decode_its, loop_steps

SSM_UPDATE = "ssm_update"  # ops/ssm.py, as the trace prints it


def is_sambay(ctx) -> bool:
    return bool(ctx["model"].get("mb_per_layer"))


def decode_step_ms(ctx):
    """Median device time of one decode step: each decode-loop execution's
    duration over the steps it ran. A step is one call of an `attention`
    kernel a layer that attends (the window layers, the full one and the
    cross layers: 16 of Phi-4-mini-flash's 32), or, where the trace holds
    none of those under that name, one `ssm_update` call a Mamba layer (9)."""
    if not is_sambay(ctx):
        return None
    m = ctx["model"]
    by_attention = sum(costs_sambay.count(m, k) for k in ("window", "full", "cross"))
    by_update = costs_sambay.count(m, "mamba")
    per = []
    for mod in decode_loop_modules(ctx):
        updates = [sum(n for k, n in calls.items() if SSM_UPDATE in k)
                   for calls in mod["kernels"]]
        for d, ka, ku in zip(mod["durations_ms"], attention_calls(mod), updates):
            if ka >= by_attention and ka % by_attention == 0:
                per.append(d / (ka // by_attention))
            elif not ka and ku >= by_update and ku % by_update == 0:
                per.append(d / (ku // by_update))
    return ctx["percentile"](per, 50) if per else None


def captured_decode(ctx) -> list:
    """The decode iterations that began inside the profiler's captures and
    ran at least one step of the decode loop."""
    return [i for i in captured(ctx, decode_its(ctx)) if loop_steps(i) > 0]


def rows_and_context(ctx, its):
    """(median rows, median context a row) of decode iterations `its`: the
    context from the full layer's live pages a step, `decode_pages_live_global`
    (a row's pages are its context rounded up to pages, so half a page a row
    is taken off: a floor)."""
    if not its:
        return None, None
    ps = ctx["ready"]["engine"]["page_size"]
    med = lambda xs: ctx["percentile"](xs, 50)
    rows = med([i["decode_seqs"] for i in its])
    pages = med([i.get("decode_pages_live_global", 0) / max(1, loop_steps(i)) / max(1, i["decode_seqs"])
                 for i in its])
    return rows, max(0.0, (pages - 0.5) * ps)


def kernel_roofline(ctx, kernel: str, pages_key: str):
    """A decode kernel's share of its HBM roofline (%): costs_sambay's floor
    of a call's bytes over the peak bandwidth, over the kernel's mean device
    time a call. Pages and rows a call: means over the decode loop's steps of
    the captured iterations."""
    import os

    import costs

    per_call_s = call_seconds(ctx, kernel)
    its = captured_decode(ctx)
    steps = sum(loop_steps(i) for i in its)
    if per_call_s is None or not is_sambay(ctx) or not steps:
        return None
    pages = sum(i.get(pages_key, 0) for i in its) / steps
    rows = sum(i["decode_seqs"] * loop_steps(i) for i in its) / steps
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    need = costs_sambay.decode_call_bytes(ctx["model"], pages, rows, ctx["ready"]["engine"]["page_size"])
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / per_call_s
