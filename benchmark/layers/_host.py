"""What the readers of the step loop's host clock share (PR 38): the window's
flight-recorder iterations (`counters.json`), each with the step thread's
seconds by phase between the two commits its `wall_s` runs between
(`host_<phase>_s`, `exposed_s`, ...; runtime/annotations.py, the door).
A program whose records lack a field reads None: the line leaves the metric
out, and nothing raises."""

# after a capture's stop the profiler writes its file (`written_s`) and the
# loop takes about a second to settle (PERF.md section 5, Jamba: the iteration
# after a stop lasted 0.95 s where its neighbours last 0.07)
SETTLE_S = 1.0


def undisturbed(ctx) -> list:
    """The window's iterations the profiler did not disturb: `ts` outside
    [start_wall, stop_wall + written_s + SETTLE_S] of every capture the run
    took (`counters.json` `trace.captures`); all of them where it took none.
    The complement of `_ssm.captured`, with the seconds after a stop left out
    as well."""
    its = ctx["counters"].get("iterations") or []
    caps = (ctx["counters"].get("trace") or {}).get("captures") or []
    spans = [(c["start_wall"], c["stop_wall"] + c.get("written_s", 0.0) + SETTLE_S)
             for c in caps]
    return [i for i in its
            if not any(a <= i.get("ts", -1.0) <= b for a, b in spans)]


def share_pct(ctx, field):
    """Sum of `field` over sum of `wall_s`, undisturbed iterations (%): a
    share of the loop's busy time. None where a record lacks the field."""
    its = undisturbed(ctx)
    wall = sum(i["wall_s"] for i in its)
    if not its or wall <= 0.0 or any(field not in i for i in its):
        return None
    return 100.0 * sum(i[field] for i in its) / wall


def mean_ms(ctx, field):
    """Mean of `field` an undisturbed iteration (ms); None as above."""
    its = undisturbed(ctx)
    if not its or any(field not in i for i in its):
        return None
    return 1e3 * sum(i[field] for i in its) / len(its)
