"""Pallas TPU prefill flash attention over the paged KV pool.

The prefill hot op: a chunk of S new query tokens per sequence attends over
the full paged context (prior prefix-cache/chunk pages + this chunk's own
pages, already written to the pool). The pool operand is the layer-stacked
pool [L, NP, PS, Hk, D], read at a scalar-prefetched layer (see
ops/paged_attention.py). The jnp path materializes
[B, Hk, G, S, C] fp32 scores in HBM — O(S·C) traffic that dominates long
prompts. This kernel streams K/V pages HBM→VMEM once per (q-block, page)
pair with flash online softmax in VMEM scratch, and skips both the DMA and
the compute for pages that are entirely masked:

- pages at/after the q-block's last causal position, and pages past
  kv_len, are clamped in the index_map to the last needed page, so the
  block index repeats and Pallas elides the copy (same trick as the decode
  kernel). A causal chunk therefore costs ~half the rectangular DMA.

Layout: q arrives [B, Hk, S*G, D] (wrapper transposes from the model's
[B, S, Hk, G, D] and merges the group axis into the rows) so a block is
[Hk, Sq*G, D] and the matmul runs as one Hk-batched [Sq*G, D] x [D, PS] —
MXU-shaped at Sq=128. The merge happens in XLA, not in the kernel: a
[.., G, D] block pads G up to a full sublane tile in VMEM (G=1 in bf16 is
16x, past the scoped VMEM limit at Hk=32), and Mosaic cannot shape-cast
every (Sq, G) split.

Positions contract (same as models/llama.py paged_attention_jnp): flat
context index c IS absolute position c; query token s of sequence b sits at
absolute position q_start[b] + s for s < q_len[b], padding after.

The reference delegates prefill attention to vLLM/TRT-LLM FlashAttention
CUDA kernels (SURVEY.md: engine tier); this is the TPU-native equivalent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import (
    scalar_operands, split_scales, stacked_pools,
)
from dynamo_tpu.parallel.mesh import AXIS_MODEL, prefill_attention_specs

NEG_INF = -1e30

# All Hk heads of a q block are resident at once: at Hk=32, Sq=128 the
# blocks, the f32 accumulators and the score temporaries come to ~19 MiB,
# past Mosaic's 16 MiB default scoped limit (a compiler default — a v5e
# core has 128 MiB of VMEM).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _prefill_kernel_body(
    # scalar prefetch
    page_table_ref,  # [B, MP] int32
    q_start_ref,  # [B] int32 absolute position of query token 0
    q_len_ref,  # [B] int32 number of valid query tokens
    kv_lens_ref,  # [B] int32 context length (incl. this chunk)
    #   (the pool's layer [1] rides next; only the index maps read it)
    win_ref,  # [1] int32 sliding window (0 = global) or None (no-window
    #   compile) — Gemma-2 alternates per layer with a traced scalar
    # blocks
    q_ref,  # [Hk, Sq*G, D] (row r is query token r // G, group r % G)
    k_ref,  # [PS, Hk, D] one token-major page (one contiguous DMA)
    v_ref,  # [PS, Hk, D]
    ks_ref,  # [PS, Hk] f32 per-vector K scales (int8 KV) or None
    vs_ref,  # [PS, Hk] f32 per-vector V scales or None
    o_ref,  # [Hk, Sq*G, D]
    # scratch (persist across the page loop)
    m_ref,  # [Hk, Sq*G, 1] f32
    l_ref,  # [Hk, Sq*G, 1] f32
    acc_ref,  # [Hk, Sq*G, D] f32
    *,
    page_size: int,
    q_block: int,
    n_groups: int,
    scale: float,
    softcap: float = 0.0,
):
    b = pl.program_id(0)
    sb = pl.program_id(1)
    i = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_start_ref[b]
    q_len = q_len_ref[b]
    kv_len = kv_lens_ref[b]
    # last absolute position any valid query row in this block can see
    blk_rows = jnp.minimum(q_len - sb * q_block, q_block)  # valid rows here
    blk_max_pos = q_start + sb * q_block + blk_rows - 1
    page_first = i * page_size
    needed = (blk_rows > 0) & (page_first <= blk_max_pos) & (page_first < kv_len)
    if win_ref is not None:
        # sliding window: the EARLIEST position any row here can see is
        # first_row_pos - w + 1; pages wholly before that are dead (their
        # DMA is already elided by the index_map's low clamp)
        w = win_ref[0]
        blk_lo = jnp.where(
            w > 0, jnp.maximum(q_start + sb * q_block - w + 1, 0), 0
        )
        needed = needed & (page_first + page_size > blk_lo)

    @pl.when(needed)
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # [Hk, Sq*G, D]
        k = k_ref[...].astype(jnp.float32)  # [PS, Hk, D]
        s = lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))), preferred_element_type=jnp.float32
        ) * scale  # [Hk, Sq*G, PS]
        if ks_ref is not None:
            # int8 KV: fold per-(token, head) K scales into the scores
            # ((PS, Hk) block transposed in-register — 2 KiB)
            s = s * ks_ref[...].T[:, None, :]
        if softcap:
            # the TRUE score (post any int8 fold), matching the jnp path
            s = softcap * jnp.tanh(s / softcap)

        row = lax.broadcasted_iota(jnp.int32, s.shape, 1) // n_groups  # sq idx
        col = lax.broadcasted_iota(jnp.int32, s.shape, 2)  # slot in page
        q_pos = q_start + sb * q_block + row
        kv_pos = page_first + col
        mask = (row < blk_rows) & (kv_pos <= q_pos) & (kv_pos < kv_len)
        if win_ref is not None:
            w = win_ref[0]
            mask = mask & ((w <= 0) | (kv_pos > q_pos - w))
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)

        l_add = jnp.sum(p, axis=2, keepdims=True)  # raw-probability denom
        if vs_ref is not None:
            p = p * vs_ref[...].T[:, None, :]  # fold V scales into p
        v = v_ref[...].astype(jnp.float32)  # [PS, Hk, D]
        pv = lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))), preferred_element_type=jnp.float32
        )  # [Hk, Sq*G, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        l_ref[...] = l_ref[...] * alpha + l_add
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _prefill_kernel(pt, qs, ql, kl, ly, q, k, v, o, m, l, acc, **kw):
    _prefill_kernel_body(pt, qs, ql, kl, None, q, k, v, None, None,
                         o, m, l, acc, **kw)


def _prefill_kernel_win(pt, qs, ql, kl, ly, win, q, k, v, o, m, l, acc,
                        **kw):
    _prefill_kernel_body(pt, qs, ql, kl, win, q, k, v, None, None,
                         o, m, l, acc, **kw)


def _prefill_kernel_int8(pt, qs, ql, kl, ly, q, k, ks, v, vs, o, m, l, acc,
                         **kw):
    _prefill_kernel_body(pt, qs, ql, kl, None, q, k, v, ks, vs,
                         o, m, l, acc, **kw)


def _prefill_kernel_int8_win(pt, qs, ql, kl, ly, win, q, k, ks, v, vs, o, m,
                             l, acc, **kw):
    _prefill_kernel_body(pt, qs, ql, kl, win, q, k, v, ks, vs,
                         o, m, l, acc, **kw)


def prefill_paged_attention_sharded(
    q: jax.Array,  # [B, S, Hk, G, D] heads sharded over `axis_name`
    k_pool: jax.Array,  # [L, NP, PS, Hk, D] (token-major, stacked)
    v_pool: jax.Array,
    page_table: jax.Array,
    q_start: jax.Array,
    q_len: jax.Array,
    kv_lens: jax.Array,
    mesh,
    axis_name: str = AXIS_MODEL,
    window=None,  # traced int32 scalar (see prefill_paged_attention)
    layer=None,  # traced int32 scalar, replicated
    *,
    q_block: int = 128,
    scale=None,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel wrapper (see decode_paged_attention_sharded): each
    model-axis shard runs the kernel over its local kv-heads."""
    from jax.sharding import PartitionSpec as P

    heads, pool, scales = prefill_attention_specs(axis_name)
    if isinstance(k_pool, dict):  # int8 KV: scales [L, NP, PS, Hk] shard
        # the same head axis
        pool = {"q": pool, "s": scales}
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    scalars = scalar_operands(layer, window)

    def part(q, k_pool, v_pool, page_table, q_start, q_len, kv_lens, layer,
             window=None):
        return prefill_paged_attention(
            q, k_pool, v_pool, page_table, q_start, q_len, kv_lens, window,
            layer, q_block=q_block, scale=scale, softcap=softcap,
            interpret=interpret,
        )

    fn = jax.shard_map(
        part, mesh=mesh,
        in_specs=(heads, pool, pool, P(None, None), P(None), P(None), P(None))
        + (P(),) * len(scalars),
        out_specs=heads, check_vma=False,
    )
    return fn(q, k_pool, v_pool, page_table, q_start, q_len, kv_lens,
              *scalars)


@functools.partial(
    jax.jit, static_argnames=("q_block", "interpret", "scale", "softcap")
)
def prefill_paged_attention(
    q: jax.Array,  # [B, S, Hk, G, D]
    k_pool: jax.Array,  # [L, NP, PS, Hk, D] stacked token-major pool (or
    #   one layer's [NP, PS, Hk, D]: see stacked_pools)
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, MP] int32
    q_start: jax.Array,  # [B] int32 absolute position of query token 0
    q_len: jax.Array,  # [B] int32 valid query tokens (rest are padding)
    kv_lens: jax.Array,  # [B] int32 context length incl. this chunk
    window=None,  # None = no-window compile; else traced int32 scalar
    #   (0 = global at runtime) — see decode_paged_attention
    layer=None,  # traced int32 scalar: the stacked pool's layer to read
    *,
    q_block: int = 128,
    scale=None,  # static score-scale override (query_pre_attn_scalar)
    softcap: float = 0.0,  # Gemma-2 logit soft capping (static; 0 = off)
    interpret: bool = False,
) -> jax.Array:
    """Returns [B, S, Hk, G, D]; padding rows (s >= q_len[b]) return 0.
    The chunk's own K/V must already be written to the pool."""
    B, S, Hk, G, D = q.shape
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    kq, vq, ks, vs = split_scales(k_pool, v_pool, layer)
    quantized = ks is not None
    _, NP, PS, _, _ = kq.shape
    MP = page_table.shape[1]
    q_block = min(q_block, S)
    while S % q_block:  # largest divisor of S at most the requested block
        q_block -= 1
    n_sblk = S // q_block
    if scale is None:
        scale = D**-0.5
    windowed = window is not None

    qt = q.transpose(0, 2, 1, 3, 4).reshape(B, Hk, S * G, D)

    def _clamp(b, sb, i, pt, qs, ql, kl, ly, *rest):
        # clamp to the page range this q-block can actually see (causal
        # top, kv_len, and — with a window — the sliding low bound):
        # repeated indices across grid steps → Pallas skips the DMA
        rows = jnp.minimum(ql[b] - sb * q_block, q_block)
        blk_max_pos = qs[b] + sb * q_block + jnp.maximum(rows, 1) - 1
        last = jnp.minimum(blk_max_pos, jnp.maximum(kl[b] - 1, 0)) // PS
        last = jnp.clip(last, 0, MP - 1)
        i_eff = jnp.minimum(i, last)
        if rest:
            (win,) = rest
            w = win[0]
            lo = jnp.where(
                w > 0, jnp.maximum(qs[b] + sb * q_block - w + 1, 0), 0
            )
            i_eff = jnp.maximum(i_eff, jnp.minimum(lo // PS, last))
        return i_eff

    def kv_index(b, sb, i, pt, qs, ql, kl, ly, *rest):
        return (ly[0], pt[b, _clamp(b, sb, i, pt, qs, ql, kl, ly, *rest)],
                0, 0, 0)

    def scale_index(b, sb, i, pt, qs, ql, kl, ly, *rest):
        return kv_index(b, sb, i, pt, qs, ql, kl, ly, *rest)[1:4]

    def q_index(b, sb, i, pt, qs, ql, kl, ly, *rest):
        return (b, 0, sb, 0)

    q_spec = pl.BlockSpec((None, Hk, q_block * G, D), q_index)
    # one token-major page of one layer = one contiguous PS*Hk*D slab
    # (single DMA)
    kv_spec = pl.BlockSpec((None, None, PS, Hk, D), kv_index)
    kw = dict(page_size=PS, q_block=q_block, n_groups=G, scale=scale,
              softcap=softcap)
    if quantized:
        kernel = functools.partial(
            _prefill_kernel_int8_win if windowed else _prefill_kernel_int8,
            **kw,
        )
        # (None, PS, Hk): minor dims are full array dims — legal tile
        s_spec = pl.BlockSpec((None, PS, Hk), scale_index)
        in_specs = [q_spec, kv_spec, s_spec, kv_spec, s_spec]
        operands = (qt, kq, ks, vq, vs)
    else:
        kernel = functools.partial(
            _prefill_kernel_win if windowed else _prefill_kernel, **kw
        )
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (qt, kq, vq)

    prefetch = (page_table, q_start, q_len, kv_lens) + scalar_operands(
        layer, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # pt, q_start, q_len, kv, layer
        #   (+ window)
        grid=(B, n_sblk, MP),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hk, q_block * G, 1), jnp.float32),
            pltpu.VMEM((Hk, q_block * G, 1), jnp.float32),
            pltpu.VMEM((Hk, q_block * G, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, S * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*prefetch, *operands)
    # [B, Hk, S*G, D] -> [B, S, Hk, G, D]
    return out.reshape(B, Hk, S, G, D).transpose(0, 2, 1, 3, 4)
