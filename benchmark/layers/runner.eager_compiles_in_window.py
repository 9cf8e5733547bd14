"""Programs compiled between the window's start and its end that no step
family saw: compile_stats()["other"], counted by the program from jax's own
compile event on each engine's step thread, all replicas. The eager slice
and index programs of the serving path land here; 0 when the warm-up met
every shape."""


def read(ctx):
    c = ctx["counters"]
    pairs = list(zip(c["at0"], c["at1"]))
    if not pairs or any("other" not in b["compile"] for _, b in pairs):
        return None
    return float(sum(b["compile"]["other"]["variants"] - a["compile"]["other"]["variants"]
                     for a, b in pairs))
