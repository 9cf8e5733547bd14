"""Iterations the profiler did not disturb whose `wall_s` is over ten times
the median of their `kind` (count): the stalls of ROADMAP S12. The record's
phases say who held one (`host_readback_s`: the device or the machine under
it; a host phase; `gc_s`; `compile_variants` growing)."""
from _host import undisturbed


def read(ctx):
    its = undisturbed(ctx)
    if not its:
        return None
    by_kind = {}
    for i in its:
        by_kind.setdefault(i["kind"], []).append(i["wall_s"])
    median = {k: ctx["percentile"](w, 50) for k, w in by_kind.items()}
    return float(sum(i["wall_s"] > 10.0 * median[i["kind"]] for i in its))
