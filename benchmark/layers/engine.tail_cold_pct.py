"""Share of the tail's decode time spent under drained plain decodes
(`decode_cold_s` over `decode_s`) (%): a decode that found nothing in flight,
after a joiner, a smaller bucket or a mixed step: the drains' share."""
from _tail import share_pct, tail


def read(ctx):
    return share_pct(tail(ctx), "decode_cold_s")
