"""Step-program variants compiled between the window's start and its end,
all families and replicas (compile_stats()). Set-up is right when this is 0."""


def read(ctx):
    c = ctx["counters"]
    return float(sum(b["compile"][f]["variants"] - a["compile"][f]["variants"]
                     for a, b in zip(c["at0"], c["at1"]) for f in b["compile"]))
