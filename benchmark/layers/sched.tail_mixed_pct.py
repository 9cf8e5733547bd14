"""Share of the tail's decode time spent under iterations that carried other
prompts' chunks, fused (`decode_mixed_s`) or as a dispatch of their own
(`decode_prefill_s`), over `decode_s` (%): what admitting other prompts cost
the tail's streams (the mixed step's price, the token budget)."""
from _tail import share_pct, tail


def read(ctx):
    return share_pct(tail(ctx), "decode_mixed_s", "decode_prefill_s")
