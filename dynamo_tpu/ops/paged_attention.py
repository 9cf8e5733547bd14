"""Pallas TPU decode paged attention.

The decode hot op: one query token per sequence attends over that
sequence's paged KV (pages scattered in a global HBM pool, owned via a page
table). The pool operand is the layer-STACKED pool [L, NP, PS, Hk, D] that
the layer scan carries, read at a scalar-prefetched layer: a Pallas call
takes whole buffers, so handing it `pool[l]` with a traced l makes XLA
copy that slab out first, once per pool per layer per step. The jnp
reference path (models/llama.py paged_attention_jnp) gathers all pages into
a dense [B, ctx] tensor per layer — an extra HBM round trip of the whole
KV working set. This kernel streams each page
HBM→VMEM once via BlockSpec index_maps driven by the scalar-prefetched page
table and accumulates flash-attention-style online softmax in VMEM scratch.

Grid: (B, MP) — page index innermost so the per-sequence running softmax
state lives across the page loop; all kv heads are processed per step. A
token-major page [PS, Hk, D] is one CONTIGUOUS slab in the pool, so each
grid step issues a single large DMA (the head-major layout needed Hk
strided chunks per page). Ragged contexts cost
only what they use: the index_map clamps pages past kv_len to the last
valid page, so consecutive grid steps see an unchanged block index and
Pallas elides the HBM→VMEM copy (and pl.when skips the compute).

The reference framework ships CUDA kernels for its block engine
(lib/llm/src/kernels/block_copy.cu, lib/kvbm-kernels/cuda/
tensor_kernels.cu); attention itself lives in vLLM. This is the TPU-native
equivalent of that hot path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.parallel.mesh import AXIS_MODEL, attention_specs

NEG_INF = -1e30


def stacked_pools(k_pool, v_pool, layer):
    """The attention kernels' pool operands: (k_pool, v_pool, layer[1]).

    A pool is layer-stacked [L, NP, PS, Hk, D] (int8 KV: a dict of "q"
    [L, NP, PS, Hk, D] and "s" [L, NP, PS, Hk]) and `layer` a traced int32
    scalar. One layer's pool [NP, PS, Hk, D] is the one-layer stack
    `a[None]` read at layer 0 (a free reshape), so both ranks run the same
    program."""
    kq = k_pool["q"] if isinstance(k_pool, dict) else k_pool
    if kq.ndim == 4:
        if layer is not None:
            raise ValueError("a per-layer pool [NP, PS, Hk, D] takes no layer")
        k_pool, v_pool = jax.tree.map(lambda a: a[None], (k_pool, v_pool))
        layer = 0
    elif layer is None:
        raise ValueError("a stacked pool [L, NP, PS, Hk, D] needs its layer")
    return k_pool, v_pool, jnp.asarray(layer, jnp.int32).reshape(1)


def scalar_operands(layer, window):
    """The operands that ride behind the page table and the lengths, as
    scalar prefetch and through shard_map: layer[1] (+ window[1])."""
    if window is None:
        return (layer,)
    return (layer, jnp.asarray(window, jnp.int32).reshape(1))


def split_scales(k_pool, v_pool, layer):
    """(k, v, k_scales, v_scales) as the pallas_call takes them: the data
    stacked [L, NP, PS, Hk, D], the int8 pools' f32 scales as THIS layer's
    [NP, PS, Hk] (None for dense pools). The scales are sliced here, in
    XLA, on purpose: Mosaic takes an operand row-major, which pads their
    minor Hk to 128 lanes (4-64x), while the `_write_kv` scatter keeps the
    stacked scales tokens-minor — handed the whole stack, XLA converted it
    between the two layouts twice a pool in EVERY layer (v5e HLO, PR 25).
    A layer's scales are 1/D of its data; the copy that hurt was the
    data's."""
    if not isinstance(k_pool, dict):
        return k_pool, v_pool, None, None
    ks, vs = (lax.dynamic_index_in_dim(p["s"], layer[0], keepdims=False)
              for p in (k_pool, v_pool))
    return k_pool["q"], v_pool["q"], ks, vs


def _decode_kernel_body(
    page_table_ref,  # [B, MP] int32 (SMEM)
    kv_lens_ref,  # [B] int32 (SMEM)
    win_ref,  # [1] int32 sliding window (0 = global) or None (no-window
    #   compile: Gemma-2 alternates sliding/global per layer with a
    #   TRACED scalar, so the window rides as a prefetch operand)
    q_ref,  # [Hk, G, D] all query heads for seq b
    k_ref,  # [PS, Hk, D] one token-major page of keys (one contiguous DMA)
    v_ref,  # [PS, Hk, D]
    ks_ref,  # [PS, Hk] f32 per-vector K scales (int8 KV) or None
    vs_ref,  # [PS, Hk] f32 per-vector V scales or None
    o_ref,  # [Hk, G, D]
    # scratch (persist across the page loop)
    m_ref,  # [Hk, G, 1] f32 running max
    l_ref,  # [Hk, G, 1] f32 running denom
    acc_ref,  # [Hk, G, D] f32 running numerator
    *,
    page_size: int,
    scale: float,
    softcap: float = 0.0,  # Gemma-2 attention-score soft capping (0 = off)
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]
    n_valid = jnp.clip(kv_len - i * page_size, 0, page_size)
    # sliding window: the decode query sits at position kv_len-1, so only
    # positions >= lo = kv_len - window are visible. Pages wholly below lo
    # contribute nothing (their DMA is already elided by the index_map's
    # low clamp); partially-covered pages mask their leading slots.
    lo = jnp.int32(0)
    if win_ref is not None:
        w = win_ref[0]
        lo = jnp.where(w > 0, jnp.maximum(kv_len - w, 0), 0)
    lo_in_page = jnp.clip(lo - i * page_size, 0, page_size)

    @pl.when((n_valid > 0) & (lo_in_page < n_valid))
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # [Hk, G, D]
        k = k_ref[...].astype(jnp.float32)  # [PS, Hk, D]
        # s[h, g, p] = q[h, g, :] · k[p, h, :] (batch dim Hk sits at k
        # axis 1 — dot_general takes batch dims at any position)
        s = lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))), preferred_element_type=jnp.float32
        ) * scale  # [Hk, G, PS]
        if ks_ref is not None:
            # int8 KV: fold the per-(token, head) K scale into the scores
            # instead of dequantizing K over D (one [Hk, 1, PS] multiply
            # replaces a [PS, Hk, D] one); the (PS, Hk) block transposes
            # in-register — 2 KiB, negligible next to the page DMA
            s = s * ks_ref[...].T[:, None, :]
        if softcap:
            # applied to the TRUE score (after any int8 scale fold),
            # matching paged_attention_jnp's order
            s = softcap * jnp.tanh(s / softcap)
        pos = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = (pos < n_valid) & (pos >= lo_in_page)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]  # [Hk, G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [Hk, G, PS]
        alpha = jnp.exp(m_prev - m_new)

        l_add = jnp.sum(p, axis=2, keepdims=True)  # BEFORE any V scaling:
        # the softmax denominator sums raw probabilities
        if vs_ref is not None:
            # fold the V scale into p before the PV matmul (same trick)
            p = p * vs_ref[...].T[:, None, :]
        v = v_ref[...].astype(jnp.float32)  # [PS, Hk, D]
        pv = lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))), preferred_element_type=jnp.float32
        )  # [Hk, G, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        l_ref[...] = l_ref[...] * alpha + l_add
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _decode_kernel(pt, kl, ly, q, k, v, o, m, l, acc, *, page_size, scale,
                   softcap=0.0):
    _decode_kernel_body(
        pt, kl, None, q, k, v, None, None, o, m, l, acc,
        page_size=page_size, scale=scale, softcap=softcap,
    )


def _decode_kernel_win(pt, kl, ly, win, q, k, v, o, m, l, acc, *,
                       page_size, scale, softcap=0.0):
    _decode_kernel_body(
        pt, kl, win, q, k, v, None, None, o, m, l, acc,
        page_size=page_size, scale=scale, softcap=softcap,
    )


def _decode_kernel_int8(pt, kl, ly, q, k, ks, v, vs, o, m, l, acc, *,
                        page_size, scale, softcap=0.0):
    _decode_kernel_body(
        pt, kl, None, q, k, v, ks, vs, o, m, l, acc,
        page_size=page_size, scale=scale, softcap=softcap,
    )


def _decode_kernel_int8_win(pt, kl, ly, win, q, k, ks, v, vs, o, m, l, acc,
                            *, page_size, scale, softcap=0.0):
    _decode_kernel_body(
        pt, kl, win, q, k, v, ks, vs, o, m, l, acc,
        page_size=page_size, scale=scale, softcap=softcap,
    )


def decode_paged_attention_sharded(
    q: jax.Array,  # [B, Hk, G, D] heads sharded over `axis_name`
    k_pool: jax.Array,  # [L, NP, PS, Hk, D] heads sharded over `axis_name`
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, MP] replicated
    kv_lens: jax.Array,  # [B] replicated
    mesh,
    axis_name: str = AXIS_MODEL,
    window=None,  # traced int32 scalar (see decode_paged_attention)
    layer=None,  # traced int32 scalar, replicated
    *,
    scale=None,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel wrapper: attention is independent per kv-head, and
    the KV pool shards kv-heads over the model axis (ShardingPolicy), so
    each shard runs the kernel on its local heads — zero collectives (the
    block all-reduce happens later in the out-projection as usual)."""
    from jax.sharding import PartitionSpec as P

    heads, pool, scales = attention_specs(axis_name)
    if isinstance(k_pool, dict):  # int8 KV: scales [L, NP, PS, Hk] shard
        # the same head axis
        pool = {"q": pool, "s": scales}
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    scalars = scalar_operands(layer, window)

    def part(q, k_pool, v_pool, page_table, kv_lens, layer, window=None):
        return decode_paged_attention(
            q, k_pool, v_pool, page_table, kv_lens, window, layer,
            scale=scale, softcap=softcap, interpret=interpret,
        )

    fn = jax.shard_map(
        part,
        mesh=mesh,
        in_specs=(heads, pool, pool, P(None, None), P(None))
        + (P(),) * len(scalars),
        out_specs=heads,
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, page_table, kv_lens, *scalars)


@functools.partial(
    jax.jit, static_argnames=("interpret", "scale", "softcap")
)
def decode_paged_attention(
    q: jax.Array,  # [B, Hk, G, D]
    k_pool: jax.Array,  # [L, NP, PS, Hk, D] the stacked token-major key
    #   pool (or one layer's [NP, PS, Hk, D]: see stacked_pools)
    v_pool: jax.Array,
    page_table: jax.Array,  # [B, MP] int32
    kv_lens: jax.Array,  # [B] int32 (context length incl. current token)
    window=None,  # None = no-window compile; else a traced int32 scalar
    #   (0 = global at runtime) — Gemma-2 alternates per layer in the scan
    layer=None,  # traced int32 scalar: the layer of the stacked pool to
    #   read; rides the scan as a prefetch operand like `window`
    *,
    scale=None,  # static score-scale override (query_pre_attn_scalar)
    softcap: float = 0.0,  # Gemma-2 logit soft capping (static; 0 = off)
    interpret: bool = False,
) -> jax.Array:
    """Returns [B, Hk, G, D]. KV for the current token must already be
    written to the pool (same contract as paged_attention_jnp)."""
    B, Hk, G, D = q.shape
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    kq, vq, ks, vs = split_scales(k_pool, v_pool, layer)
    quantized = ks is not None
    _, NP, PS, _, _ = kq.shape
    MP = page_table.shape[1]
    if scale is None:
        scale = D**-0.5
    windowed = window is not None

    def _clamp(b, i, pt, kl, ly, *rest):
        # clamp past-the-end pages to the last valid page: the block index
        # then repeats across those grid steps and Pallas skips the DMA,
        # so a 128-token context in an 8192-token table costs 2 page
        # copies, not 128. With a sliding window, pages wholly below the
        # window likewise clamp UP to the first live page.
        last = jnp.maximum(kl[b] - 1, 0) // PS
        i_eff = jnp.minimum(i, last)
        if rest:
            (win,) = rest
            w = win[0]
            lo = jnp.where(w > 0, jnp.maximum(kl[b] - w, 0), 0)
            i_eff = jnp.maximum(i_eff, jnp.minimum(lo // PS, last))
        return i_eff

    def kv_index(b, i, pt, kl, ly, *rest):
        return (ly[0], pt[b, _clamp(b, i, pt, kl, ly, *rest)], 0, 0, 0)

    def scale_index(b, i, pt, kl, ly, *rest):
        return kv_index(b, i, pt, kl, ly, *rest)[1:4]

    def fixed_index(b, i, pt, kl, ly, *rest):
        return (b, 0, 0, 0)

    q_spec = pl.BlockSpec((None, Hk, G, D), fixed_index)
    # one token-major page of one layer = one contiguous PS*Hk*D slab: a
    # single DMA, with a legal (PS, Hk, D) tile (minor dims (Hk, D))
    kv_spec = pl.BlockSpec((None, None, PS, Hk, D), kv_index)
    kw = dict(page_size=PS, scale=scale, softcap=softcap)
    if quantized:
        kernel = functools.partial(
            _decode_kernel_int8_win if windowed else _decode_kernel_int8, **kw
        )
        # (None, PS, Hk): minor dims are full array dims — legal tile
        s_spec = pl.BlockSpec((None, PS, Hk), scale_index)
        in_specs = [q_spec, kv_spec, s_spec, kv_spec, s_spec]
        operands = (q, kq, ks, vq, vs)
    else:
        kernel = functools.partial(
            _decode_kernel_win if windowed else _decode_kernel, **kw
        )
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (q, kq, vq)

    prefetch = (page_table, kv_lens) + scalar_operands(layer, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # page_table, kv_lens, layer
        #   (+ window)
        grid=(B, MP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, Hk, G, D), fixed_index),
        scratch_shapes=[
            pltpu.VMEM((Hk, G, 1), jnp.float32),
            pltpu.VMEM((Hk, G, 1), jnp.float32),
            pltpu.VMEM((Hk, G, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, D), q.dtype),
        interpret=interpret,
    )(*prefetch, *operands)
    return out
