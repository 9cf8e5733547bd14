"""Per-layer cost ablations: KV-write scatter, attention impl/size.

Monkeypatches llama internals before jit so the traced graph omits the
ablated op — semantics are wrong, timing is the point.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dynamo_tpu.models import llama
from bench_ablate import make_runner, time_decode  # noqa: E402
from dynamo_tpu.models.config import get_config

import dynamo_tpu  # noqa: E402

dynamo_tpu.enable_compilation_cache()
cfg = get_config("llama-3.2-3b")

base = time_decode(make_runner(cfg), cfg)
print(f"baseline           step: {base:.2f} ms", flush=True)

orig_write = llama._write_kv
llama._write_kv = lambda pool, *a, **k: pool
nw = time_decode(make_runner(cfg), cfg)
llama._write_kv = orig_write
print(f"no kv-write        step: {nw:.2f} ms  (scatter cost {base - nw:.2f})",
      flush=True)

orig_attn = llama.paged_attention_jnp


def cheap_attn(q, k_pool_l, v_pool_l, page_table, q_positions, kv_lens,
               return_stats=False):
    out = q  # [B, S, Hk, G, Dh] passthrough
    return out


llama.paged_attention_jnp = cheap_attn
na = time_decode(make_runner(cfg, attn_impl="jnp"), cfg)
llama.paged_attention_jnp = orig_attn
print(f"no attention (jnp) step: {na:.2f} ms  (attn cost {base - na:.2f})",
      flush=True)

llama._write_kv = lambda pool, *a, **k: pool
llama.paged_attention_jnp = cheap_attn
nn = time_decode(make_runner(cfg, attn_impl="jnp"), cfg)
llama._write_kv = orig_write
llama.paged_attention_jnp = orig_attn
print(f"neither            step: {nn:.2f} ms", flush=True)
