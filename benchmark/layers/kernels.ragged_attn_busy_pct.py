"""Device time in the ragged (mixed-step) attention kernel over device busy
time, from the trace (%): the Mosaic custom calls whose name holds `ragged`
(`ragged_paged_attention*` of ops/ragged_paged_attention.py, and
`window_attention_ragged*`, the same kernel under the name a window-pool
model gives its window layers' calls), `total_s` from the trace's per-kernel
table over `busy_s`. A part of `kernels.attn_busy_pct`, which also counts the
decode and prefill kernels. None where the trace holds no such kernel (a cell
whose traffic bypasses the ragged program, an untraced run)."""

KERNEL = "ragged"  # both names hold it, as the trace prints them


def read(ctx):
    tr = ctx.get("trace") or {}
    mine = [k["total_s"] for name, k in (tr.get("kernels") or {}).items() if KERNEL in name]
    if not tr.get("busy_s") or not mine:
        return None
    return 100.0 * sum(mine) / tr["busy_s"]
