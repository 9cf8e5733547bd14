"""Device time in the Pallas attention kernels over device busy time, from
the trace (%): the Mosaic custom calls whose name holds `attention` (decode,
ragged, prefill, latent; `_common.attention_calls` picks the same), summed
from the trace's per-kernel table. The expert and state-space kernels
(`routed_experts`, `ssm_update`, `ssm_scan`) are Mosaic calls too and are
not counted: until PR 43 this read every Mosaic call (`kernel_s`), and was
mostly `routed_experts` on the two routed cells."""


def read(ctx):
    tr = ctx.get("trace") or {}
    attn = [k["total_s"] for name, k in (tr.get("kernels") or {}).items() if "attention" in name]
    if not tr.get("busy_s") or not attn:
        return None
    return 100.0 * sum(attn) / tr["busy_s"]
