"""Share of the tail's decode time under no class of iteration the rule
names (`decode_other_s`) or under the loop's idle passes (`decode_wait_s`),
over `decode_s` (%): near zero on a loaded cell; above a few percent the rule
(flight_recorder.iteration_class) has a hole."""
from _tail import share_pct, tail


def read(ctx):
    return share_pct(tail(ctx), "decode_other_s", "decode_wait_s")
