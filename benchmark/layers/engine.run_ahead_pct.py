"""Share of the window's decode iterations (`kind == "decode"`) that were
enqueued ahead: planned, staged and dispatched before the iteration before
them was read back (flight recorder `ahead`, PR 37) (%). The rest drained:
`drain` says why."""


def read(ctx):
    dec = [i for i in ctx["counters"].get("iterations") or [] if i.get("kind") == "decode"]
    if not dec or any("ahead" not in i for i in dec):
        return None
    return 100.0 * sum(bool(i["ahead"]) for i in dec) / len(dec)
