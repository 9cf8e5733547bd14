"""Share of the tail's decode time spent under plain decodes enqueued ahead
(`decode_ahead_s` over `decode_s`, the tail of `_tail.tail`) (%): what a
faster decode step or a cheaper run-ahead iteration can move of
`tpot_p95_ms`."""
from _tail import share_pct, tail


def read(ctx):
    return share_pct(tail(ctx), "decode_ahead_s")
