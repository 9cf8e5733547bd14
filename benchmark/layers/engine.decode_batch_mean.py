"""Mean rows per decode iteration in the window (flight recorder), over
the iterations that decoded at all."""


def read(ctx):
    rows = [i["decode_seqs"] for i in ctx["counters"]["iterations"] if i["decode_seqs"] > 0]
    return sum(rows) / len(rows) if rows else None
