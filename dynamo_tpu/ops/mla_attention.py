"""Pallas TPU decode attention for MLA (DeepSeek latent-cache) models.

The MLA decode hot op in absorbed form: each sequence's single query
token carries per-head absorbed vectors q = [q_absorbed ; q_rope]
([H, d_c + d_rh]) and attends over the sequence's paged LATENT cache
([NP, PS, d_c + d_rh] — one vector per token, no heads). Scores are
q · latent; values are the latent's first d_c columns — so ONE page DMA
feeds both the K and the V side of the computation (the GQA kernel
needs two pools; MLA's cache compression pays again here in bandwidth).

Same streaming structure as ops/paged_attention.py: grid (B, MP), page
index innermost, scalar-prefetched page table driving BlockSpec index
maps with past-the-end pages clamped (repeat block index → Pallas elides
the copy), online-softmax state in VMEM scratch.

The pool operand is the latent pool as the layer scan carries it,
[L, NP, PS, 1, Dl] (int8: the dict of "q" [L, NP, PS, 1, Dl] and "s"
[L, NP, PS, 1]), read in place at `layer`, a traced int32 scalar that
rides in scalar prefetch behind the page table and the lengths: the page
block's index map returns (layer, page, 0, 0) over the free reshape
[L, NP, PS, Dl], so no layer's slab is copied out in front of the call
(ops/paged_attention.py `stacked_pools`, whose convention this is: one
layer's [NP, PS, 1, Dl] is the one-layer stack read at layer 0 and takes
no `layer`). The int8 pool's scales alone are sliced per layer outside
the call (`split_scales`: 1/Dl of the data; handed the whole scale stack,
the program compiled for a v5e lays it out anew inside the layer loop).
A slab of a few tens of MB was not a plain cost: XLA wrote it to on-chip
memory and the one-page-a-step DMAs came back faster from there than
they do from HBM (mistral-small-4-119b at 768 pages, 83 -> 117 us a
call, which is what its copy cost; at ling-3.0-flash-vl's 4096 pages the
slab was 335 MB in HBM, 3 ms of a 10 ms decode step: PERF.md section 6,
PR 51). Several pages a grid step is the cure for the first, not a slab.

Tiling note: the latent dim for DeepSeek-V3 is 576 = 4.5 x 128 lanes;
Pallas pads the last tile. Splitting the score matmul into an aligned
512-wide latent part and a 64-wide rope part would avoid the padding —
measured on hardware before bothering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import split_scales, stacked_pools
from dynamo_tpu.parallel.mesh import AXIS_MODEL, SPEC_MLA_LATENT_POOL

NEG_INF = -1e30


def _mla_kernel_body(
    page_table_ref,  # [B, MP] int32 (SMEM, scalar-prefetched)
    kv_lens_ref,  # [B] int32 (SMEM)
    layer_ref,  # [1] int32 (SMEM): the index maps' alone
    q_ref,  # [H, Dl] absorbed+rope query for seq b
    lat_ref,  # [PS, Dl] one latent page (single contiguous DMA)
    ls_ref,  # [1, PS] f32 per-token latent scales (int8 pool) or None
    o_ref,  # [H, dc]
    m_ref,  # [H, 1] f32 running max
    l_ref,  # [H, 1] f32 running denom
    acc_ref,  # [H, dc] f32 running numerator
    *,
    page_size: int,
    scale: float,
    dc: int,
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

    @pl.when(n_valid > 0)
    def _compute():
        q = q_ref[...].astype(jnp.float32)  # [H, Dl]
        lat = lat_ref[...].astype(jnp.float32)  # [PS, Dl]
        s = lax.dot_general(
            q, lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, PS]
        if ls_ref is not None:
            # int8 latent: fold the per-token scale into the scores —
            # one [1, PS] multiply instead of dequantizing over Dl
            s = s * ls_ref[...]
        valid = lax.broadcasted_iota(jnp.int32, s.shape, 1) < n_valid
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]  # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [H, PS]
        alpha = jnp.exp(m_prev - m_new)
        l_add = jnp.sum(p, axis=1, keepdims=True)  # raw-probability denom
        if ls_ref is not None:
            # same scale dequantizes the VALUE side (values are the
            # latent's first d_c columns of the same vector)
            p = p * ls_ref[...]
        pv = lax.dot_general(
            p, lat[:, :dc], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [H, dc]
        acc_ref[...] = acc_ref[...] * alpha + pv
        l_ref[...] = l_ref[...] * alpha + l_add
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _mla_kernel(pt, kl, ly, q, lat, o, m, l, acc, **kw):
    _mla_kernel_body(pt, kl, ly, q, lat, None, o, m, l, acc, **kw)


def _mla_kernel_int8(pt, kl, ly, q, lat, ls, o, m, l, acc, **kw):
    _mla_kernel_body(pt, kl, ly, q, lat, ls, o, m, l, acc, **kw)


def _latent_operands(lat_pool, layer):
    """(latents [L, NP, PS, Dl], this layer's scales [NP, 1, PS] or None,
    layer[1]) as the pallas_calls take them, by `stacked_pools`' rule."""
    lat_pool, _, layer = stacked_pools(lat_pool, None, layer)
    lq, _, ls, _ = split_scales(lat_pool, lat_pool, layer)
    L, NP, PS, _, Dl = lq.shape
    if ls is not None:
        # [NP, 1, PS]: a rank-1 (PS,) block is not a legal TPU tile; a
        # (1, PS) block whose dims equal the array's is
        ls = ls.reshape(NP, 1, PS)
    return lq.reshape(L, NP, PS, Dl), ls, layer


@functools.partial(jax.jit, static_argnames=("dc", "scale", "interpret"))
def decode_mla_attention(
    q: jax.Array,  # [B, H, Dl] absorbed+rope queries
    lat_pool: jax.Array,  # [L, NP, PS, 1, Dl] the stacked latent pool (or
    #   one layer's [NP, PS, 1, Dl]: see stacked_pools)
    page_table: jax.Array,  # [B, MP] int32
    kv_lens: jax.Array,  # [B] int32 (context incl. current token)
    layer=None,  # traced int32 scalar: the layer of the stacked pool to
    #   read; rides the scan as a prefetch operand
    *,
    dc: int,  # latent (value) width = kv_lora_rank
    scale: float,  # score scale ((d_nope + d_rh)^-0.5 [* yarn mscale^2])
    interpret: bool = False,
) -> jax.Array:
    """Returns the attended latents [B, H, dc] (the caller lifts them
    through W_UV). The current token's latent must already be written.
    `lat_pool` may be the int8 dict ({"q": [L,NP,PS,1,Dl] int8, "s":
    [L,NP,PS,1] f32}) — scales fold into scores/values per token."""
    B, H, Dl = q.shape
    lat, ls, layer = _latent_operands(lat_pool, layer)
    PS = lat.shape[2]
    MP = page_table.shape[1]

    def page_index(b, i, pt, kl, ly):  # the layer's scales: [NP, 1, PS]
        last = jnp.maximum(kl[b] - 1, 0) // PS
        return (pt[b, jnp.minimum(i, last)], 0, 0)

    def lat_index(b, i, pt, kl, ly):
        return (ly[0],) + page_index(b, i, pt, kl, ly)

    in_specs = [
        pl.BlockSpec((None, H, Dl), lambda b, i, *_: (b, 0, 0)),
        pl.BlockSpec((None, None, PS, Dl), lat_index),
    ]
    operands = (q, lat)
    kernel = _mla_kernel
    if ls is not None:
        in_specs.append(pl.BlockSpec((None, 1, PS), page_index))
        operands = operands + (ls,)
        kernel = _mla_kernel_int8
    prefetch = (page_table, kv_lens, layer)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, MP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, H, dc), lambda b, i, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, page_size=PS, scale=scale, dc=dc),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dc), q.dtype),
        interpret=interpret,
    )(*prefetch, *operands)


def _mla_prefill_kernel(
    page_table_ref,  # [B, MP] int32
    q_start_ref,  # [B] int32
    q_len_ref,  # [B] int32
    kv_lens_ref,  # [B] int32
    layer_ref,  # [1] int32: the index maps' alone
    q_ref,  # [Sq, H, Dl] one query block
    lat_ref,  # [PS, Dl] one latent page
    o_ref,  # [Sq, H, dc]
    m_ref,  # [Sq*H, 1] f32
    l_ref,  # [Sq*H, 1] f32
    acc_ref,  # [Sq*H, dc] f32
    *,
    page_size: int,
    q_block: int,
    scale: float,
    dc: int,
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
    blk_rows = jnp.minimum(q_len - sb * q_block, q_block)
    blk_max_pos = q_start + sb * q_block + blk_rows - 1
    page_first = i * page_size
    needed = (blk_rows > 0) & (page_first <= blk_max_pos) & (page_first < kv_len)

    @pl.when(needed)
    def _compute():
        Sq, H, Dl = q_ref.shape
        q = q_ref[...].astype(jnp.float32).reshape(Sq * H, Dl)
        lat = lat_ref[...].astype(jnp.float32)  # [PS, Dl]
        s = lax.dot_general(
            q, lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [Sq*H, PS]
        row = lax.broadcasted_iota(jnp.int32, s.shape, 0) // H
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_pos = q_start + sb * q_block + row
        kv_pos = page_first + col
        mask = (row < blk_rows) & (kv_pos <= q_pos) & (kv_pos < kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_add = jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p, lat[:, :dc], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Sq*H, dc]
        acc_ref[...] = acc_ref[...] * alpha + pv
        l_ref[...] = l_ref[...] * alpha + l_add
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        Sq, H, dcw = o_ref.shape
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype).reshape(Sq, H, dcw)


@functools.partial(jax.jit, static_argnames=("dc", "scale", "q_block", "interpret"))
def prefill_mla_attention(
    q: jax.Array,  # [B, S, H, Dl] absorbed+rope queries (chunk)
    lat_pool: jax.Array,  # [L, NP, PS, 1, Dl] the stacked latent pool (or
    #   one layer's [NP, PS, 1, Dl]: see stacked_pools)
    page_table: jax.Array,  # [B, MP]
    q_start: jax.Array,  # [B] absolute position of query token 0
    q_len: jax.Array,  # [B] valid query tokens
    kv_lens: jax.Array,  # [B] context incl. this chunk
    layer=None,  # traced int32 scalar: the layer of the stacked pool to read
    *,
    dc: int,
    scale: float,
    q_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash-style MLA prefill over latent pages (one DMA per page feeds
    scores AND values; causally-dead/past-kv pages are clamped in the
    index_map so Pallas elides their copies). Returns the attended
    latents [B, S, H, dc]; padding rows return 0. Same positions
    contract as ops/flash_prefill.py."""
    B, S, H, Dl = q.shape
    if isinstance(lat_pool, dict):
        raise NotImplementedError("the flash MLA prefill over an int8 pool")
    lat, _, layer = _latent_operands(lat_pool, layer)
    PS = lat.shape[2]
    MP = page_table.shape[1]
    # VMEM budget: the f32 acc scratch is q_block*H x dc — at flagship MLA
    # dims (H=128, dc=512) a 128-row block would need ~34MiB of scratch
    # alone. Cap the block so acc stays ~<=4MiB; tiny test dims keep the
    # requested block. The query block, its f32 copy and the scores grow
    # with the block's rows (q_block x H) whatever dc is: 2048 rows is what
    # the two caps above come to at the geometries the chip has run (128
    # heads at rank 512: 16 x 128; 16 heads at rank 512: 128 x 16), and at
    # 32 heads and rank 256 (Mistral-Small-4) the acc cap alone allows 4096,
    # which Mosaic refuses (19.7 MB of scoped VMEM against 16).
    # At 128 heads and rank 512 with a rotary key of 64 (DeepSeek-V3.2, the
    # cached vector 576 wide in 640 lanes) 2048 rows are 16.6 MB against 16:
    # the block's rows times the f32 query and acc they each cost stay
    # under 6 MiB (1365 rows there, 8 x 128 after the divisor walk below;
    # 2457 at rank 256, so the 2048 above still binds there).
    lanes = -(-Dl // 128) * 128
    q_block = min(q_block, max(8, (4 << 20) // max(H * dc * 4, 1)),
                  max(8, 2048 // H),
                  max(8, (6 << 20) // ((lanes + dc) * 4 * H)))
    q_block = min(q_block, S)
    while S % q_block:
        q_block -= 1
    n_sblk = S // q_block

    def lat_index(b, sb, i, pt, qs, ql, kl, ly):
        rows = jnp.minimum(ql[b] - sb * q_block, q_block)
        blk_max_pos = qs[b] + sb * q_block + jnp.maximum(rows, 1) - 1
        last = jnp.minimum(blk_max_pos, jnp.maximum(kl[b] - 1, 0)) // PS
        last = jnp.clip(last, 0, MP - 1)
        return (ly[0], pt[b, jnp.minimum(i, last)], 0, 0)

    prefetch = (page_table, q_start, q_len, kv_lens, layer)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_sblk, MP),
        in_specs=[
            pl.BlockSpec((None, q_block, H, Dl),
                         lambda b, sb, i, *_: (b, sb, 0, 0)),
            pl.BlockSpec((None, None, PS, Dl), lat_index),
        ],
        out_specs=pl.BlockSpec(
            (None, q_block, H, dc), lambda b, sb, i, *_: (b, sb, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((q_block * H, 1), jnp.float32),
            pltpu.VMEM((q_block * H, 1), jnp.float32),
            pltpu.VMEM((q_block * H, dc), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _mla_prefill_kernel, page_size=PS, q_block=q_block,
            scale=scale, dc=dc,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H, dc), q.dtype),
        interpret=interpret,
    )(*prefetch, q, lat)


def prefill_mla_attention_sharded(
    q: jax.Array,  # [B, S, H, Dl] heads sharded over `axis_name`
    lat_pool: jax.Array,  # [L, NP, PS, 1, Dl] REPLICATED (Hk=1)
    page_table: jax.Array,
    q_start: jax.Array,
    q_len: jax.Array,
    kv_lens: jax.Array,
    mesh,
    axis_name: str = AXIS_MODEL,
    layer=None,  # traced int32 scalar, replicated
    *,
    dc: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel wrapper for the flash MLA prefill: per-head
    independence means each shard runs the kernel on its local heads
    against the replicated latent pool — zero collectives (the block
    all-reduce happens in the out-projection as usual; the
    decode_mla_attention_sharded pattern applied to the chunk path, so
    TP meshes no longer fall back to the jnp gather)."""
    from jax.sharding import PartitionSpec as P

    lat_pool, _, layer = stacked_pools(lat_pool, None, layer)
    fn = jax.shard_map(
        functools.partial(
            prefill_mla_attention, dc=dc, scale=scale, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(P(None, None, axis_name, None), SPEC_MLA_LATENT_POOL,
                  P(None, None), P(None), P(None), P(None), P()),
        out_specs=P(None, None, axis_name, None),
        check_vma=False,
    )
    return fn(q, lat_pool, page_table, q_start, q_len, kv_lens, layer)


def decode_mla_attention_sharded(
    q: jax.Array,  # [B, H, Dl] heads sharded over `axis_name`
    lat_pool: jax.Array,  # [L, NP, PS, 1, Dl] REPLICATED (Hk=1 — no head
    #   axis to shard; the latent pool is small by design)
    page_table: jax.Array,
    kv_lens: jax.Array,
    mesh,
    axis_name: str = AXIS_MODEL,
    layer=None,  # traced int32 scalar, replicated
    *,
    dc: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel wrapper: per-head independence means each shard
    runs the kernel on its local heads against the replicated latent pool
    — zero collectives (the block all-reduce happens in the
    out-projection as usual)."""
    from jax.sharding import PartitionSpec as P

    lat_pool, _, layer = stacked_pools(lat_pool, None, layer)
    fn = jax.shard_map(
        functools.partial(
            decode_mla_attention, dc=dc, scale=scale, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(P(None, axis_name, None), SPEC_MLA_LATENT_POOL,
                  P(None, None), P(None), P()),
        out_specs=P(None, axis_name, None),
        check_vma=False,
    )
    return fn(q, lat_pool, page_table, kv_lens, layer)
