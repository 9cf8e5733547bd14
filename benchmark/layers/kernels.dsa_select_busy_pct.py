"""Device time in the select kernel of a decode step over device busy time,
from the trace (%): the Mosaic custom call `dsa_select` (ops/dsa_select.py:
the indexer's scores of a row to the pool cells of its best `index_topk`
tokens, a threshold select and a compaction, one call a layer of a decode
step past `index_topk`), `total_s` from the trace's per-kernel table over
`busy_s`. Its name holds no `attention`, so `kernels.attn_busy_pct` does not
count it. None where the trace holds no such kernel (a program that sorts, an
untraced run, another cell)."""

KERNEL = "dsa_select"  # ops/dsa_select.py, as the trace prints it


def read(ctx):
    tr = ctx.get("trace") or {}
    mine = [k["total_s"] for name, k in (tr.get("kernels") or {}).items() if KERNEL in name]
    if not tr.get("busy_s") or not mine:
        return None
    return 100.0 * sum(mine) / tr["busy_s"]
