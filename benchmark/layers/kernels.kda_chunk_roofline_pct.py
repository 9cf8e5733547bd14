"""The chunk kernel's share of its roofline (%): the least time a call could
take, the larger of its operations over the chip's peak bf16 FLOP/s and its
bytes over the peak HBM bandwidth (costs_kda.py; peaks.json: the bytes bind,
37 operations a byte), over the kernel's measured time a call (the trace's
`kda_chunk`: device seconds over calls; one call is one KDA layer of one
prefill chunk: the walk over its blocks of 64 tokens; the XLA matmuls that
build the blocks' operands are not in it). Tokens a call: the mean over the
chunks of the iterations that began inside the profiler's captures
(`kda_chunk_tokens`, `kda_chunk_segments` of the flight recorder). A chunk is
padded to its bucket and the kernel walks the padding's blocks too; the
floor counts the real tokens. None where the trace holds no such kernel."""
import os

import costs
import costs_kda
from _kda import captured, kernel_seconds_a_call

KERNEL = "kda_chunk"  # ops/kda.py, as the trace prints it


def read(ctx):
    per_call_s = kernel_seconds_a_call(ctx, KERNEL)
    its = [i for i in ctx["counters"]["iterations"] if i.get("kda_chunk_segments")]
    if not per_call_s or not its:
        return None
    its = captured(ctx, its)
    tokens = sum(i["kda_chunk_tokens"] for i in its) / sum(i["kda_chunk_segments"] for i in its)
    peaks = costs.load_peaks(os.path.join(ctx["here"], "peaks.json"), ctx["ready"]["device"]["kind"])
    least = max(costs_kda.kda_chunk_call_flops(ctx["model"], tokens) / peaks["bf16_flops_per_s"],
                costs_kda.kda_chunk_call_bytes(ctx["model"], tokens) / peaks["hbm_bytes_per_s"])
    return 100.0 * least / per_call_s
