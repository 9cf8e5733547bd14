"""The share of the cached context a decode step of a model with an indexer
attends to (%): `dsa_sel_tokens / dsa_ctx_tokens` over the window's decode
iterations (the flight recorder's counters: per decode row and fused step the
tokens one layer's indexer scored, and min(that, index_topk), the tokens its
attention read). None where the program records neither (a model without an
indexer, a program older than the counters)."""


def read(ctx):
    dec = [i for i in ctx["counters"]["iterations"] if i["decode_seqs"] > 0]
    seen = sum(i.get("dsa_ctx_tokens", 0) for i in dec)
    if not seen:
        return None
    return 100.0 * sum(i.get("dsa_sel_tokens", 0) for i in dec) / seen
