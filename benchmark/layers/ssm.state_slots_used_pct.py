"""Median over the window's decode iterations of the state slots in use over
the slots there are (%): `state_slots_used` / `state_slots_total` of the
flight recorder (the scratch slot is in neither). There is one slot for every
row of `max-batch`, so this is the share of the batch's rows whose sequences
are alive: at 100 the batch is full and the next request waits for a row.
None where the program records no slots (a model without state-space
layers)."""


def read(ctx):
    dec = [i for i in ctx["counters"]["iterations"]
           if i["decode_seqs"] > 0 and i.get("state_slots_total")]
    if not dec:
        return None
    return ctx["percentile"](
        [100.0 * i["state_slots_used"] / i["state_slots_total"] for i in dec], 50)
