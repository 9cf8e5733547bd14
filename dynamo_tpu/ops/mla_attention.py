"""Pallas TPU decode attention for MLA (DeepSeek latent-cache) models.

The MLA decode hot op in absorbed form: each sequence's single query
token carries per-head absorbed vectors q = [q_absorbed ; q_rope]
([H, d_c + d_rh]) and attends over the sequence's paged LATENT cache
([NP, PS, d_c + d_rh] — one vector per token, no heads). Scores are
q · latent; values are the latent's first d_c columns — so ONE page DMA
feeds both the K and the V side of the computation (the GQA kernel
needs two pools; MLA's cache compression pays again here in bandwidth).

The decode kernel is the decode walk's third caller (ops/paged_attention.py:
`decode_walk`, `Walk`, `_decode_kernel_body`, `_pages_by_tiles`), not a
third copy: its grid is the walk's list of the rows' live (row, step)
pairs, a traced bound, so a call costs what its rows hold and not what
the page table could (16 rows under a table 64 wide were 1024 grid steps
for ~45 live pages); a row's running softmax is initialised on its first
live step and written out on its last, and a row with no live page (a pad
row) is never visited: the wrapper defines its output, as 0. A step
brings `tiles` pages of the row (`step_tiles` of the page's bytes, ONE
block for keys and values: eight at rank 256 and at rank 512 under every
table the cells have), each a block of its own on the same operand found
through the walk's filled-in page table, so their DMAs are in flight
together, and takes them to the MXU as they lie, in the pool's dtype,
stacked on rows: scores q . lat with float32 accumulation, the values the
first d_c columns of the same tiles through `_pv_exact`; scores, softmax
state and accumulator float32. That, not a slab, is what hides HBM
latency: one page a step each DMA paid it alone, 117 us a call on
mistral-small-4-119b's step where the live pages' bytes are 3 us
(PERF.md section 6, PRs 51 and 52). An int8 pool takes its pages one a
step (`page_routine`'s rule), its per-token scale folded into scores and
probabilities (`_latent_page_int8`), on the same list. A caller that runs
many layers on one set of lengths builds the walk once and hands it in
(`latent_walk`; models/llama.py and models/ling.py do, above their layer
scans). The prefill kernel keeps the older structure: grid (B, query
blocks, MP), page index innermost, scalar-prefetched page table driving
BlockSpec index maps with past-the-end pages clamped (repeat block index
-> Pallas elides the copy), online-softmax state in VMEM scratch.

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

from dynamo_tpu.ops.paged_attention import (
    _PAGE_ROUTINES, Walk, _decode_kernel_body, _div, decode_walk,
    split_scales, stacked_pools,
)
from dynamo_tpu.parallel.mesh import AXIS_MODEL, SPEC_MLA_LATENT_POOL

NEG_INF = -1e30


def _latent_page_int8(q_ref, lat_refs, v_refs, ls_ref, vs_ref, m_ref, l_ref,
                      acc_ref, n_valid, lo_in_page, *, scale, softcap):
    """One int8 latent page into the running softmax, the walk's per-page
    routine (ops/paged_attention.py `_decode_kernel_body` calls it like
    its own): `page_routine`'s rule, an int8 pool takes its pages one a
    step, at one KV head on the 4-d view. The per-token scale [1, PS] folds
    into the scores, one multiply in place of a dequantisation over Dl,
    and, the values being the same vector's leading columns, into the
    probabilities on their way to the value product."""
    del v_refs, vs_ref, lo_in_page, softcap  # one block, one scale, no window
    (lat_ref,) = lat_refs
    q = q_ref[...].astype(jnp.float32)  # [H, Dl]
    lat = lat_ref[...].astype(jnp.float32)  # [PS, Dl]
    s = lax.dot_general(
        q, lat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale * ls_ref[...]  # [H, PS]
    valid = lax.broadcasted_iota(jnp.int32, s.shape, 1) < n_valid
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]  # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [H, PS]
    alpha = jnp.exp(m_prev - m_new)
    l_add = jnp.sum(p, axis=1, keepdims=True)  # raw-probability denom
    pv = lax.dot_general(
        p * ls_ref[...], lat[:, :acc_ref.shape[-1]], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [H, dc]
    acc_ref[...] = acc_ref[...] * alpha + pv
    l_ref[...] = l_ref[...] * alpha + l_add
    m_ref[...] = m_new


def _latent_kernel(wk, pg, kl, ly, q, *refs, tiles, quantized, **kw):
    """The decode walk's body (`_decode_kernel_body`) on the latent pool:
    `pg` the walk's filled-in page table, flat; the step's `tiles` page
    blocks stand for keys AND values (`_pages_by_tiles` reads the values
    off the key tiles where the two are one object); behind them the int8
    pool's scales."""
    lat, rest = refs[:tiles], refs[tiles:]
    ls, rest = (rest[0], rest[1:]) if quantized else (None, rest)
    _decode_kernel_body(wk, pg, kl, None, q, lat, lat, ls, ls, *rest, **kw)


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


def latent_walk(H: int, lat_pool, page_table, kv_lens) -> Walk:
    """`decode_walk` for ONE call of `decode_mla_attention` at `H` query
    heads (a tensor-parallel shard's): one KV head, keys and values one
    block, no window, no sink. A model builds it once a step, above its
    layer scan (models/llama.py, models/ling.py); `attn.walk` in the HLO
    metadata says where a program builds it."""
    with jax.named_scope("attn.walk"):
        return decode_walk((1, H), lat_pool, None, page_table, kv_lens, None,
                           False)


@functools.partial(jax.jit, static_argnames=("dc", "scale", "interpret"))
def decode_mla_attention(
    q: jax.Array,  # [B, H, Dl] absorbed+rope queries
    lat_pool: jax.Array,  # [L, NP, PS, 1, Dl] the stacked latent pool (or
    #   one layer's [NP, PS, 1, Dl]: see stacked_pools)
    page_table: jax.Array,  # [B, MP] int32
    kv_lens: jax.Array,  # [B] int32 (context incl. current token)
    layer=None,  # traced int32 scalar: the layer of the stacked pool to
    #   read; rides the scan as a prefetch operand
    work=None,  # latent_walk(H, lat_pool, page_table, kv_lens), for a
    #   caller that runs many layers on one set of lengths and builds it
    #   once; None = built here. Its routine and pages a step are the call's
    *,
    dc: int,  # latent (value) width = kv_lora_rank
    scale: float,  # score scale ((d_nope + d_rh)^-0.5 [* yarn mscale^2])
    interpret: bool = False,
) -> jax.Array:
    """Returns the attended latents [B, H, dc] (the caller lifts them
    through W_UV); a row with no live page (kv_len 0) returns 0. The
    current token's latent must already be written. `lat_pool` may be the
    int8 dict ({"q": [L,NP,PS,1,Dl] int8, "s": [L,NP,PS,1] f32}) — scales
    fold into scores/values per token."""
    B, H, Dl = q.shape
    if work is None:  # dynlint: disable=DYN-J001 (the argument's absence)
        work = latent_walk(H, lat_pool, page_table, kv_lens)
    lat, ls, layer = _latent_operands(lat_pool, layer)
    PS, tiles = lat.shape[2], work.tiles
    steps = page_table.shape[1] // tiles  # the steps a row can take
    # entry w of the list is row * steps + step, and its t-th tile's page
    # stands at entry * tiles + t of the flat table: the walk's filled-in
    # one, or at one page a step the page table itself
    pages = page_table.reshape(-1) if work.pages is None else work.pages

    def row_index(w, wk, *_):
        return (_div(wk[w], steps), 0, 0)

    def page_index(t, w, wk, pg, *_):  # the layer's scales: [NP, 1, PS]
        return (pg[wk[w] * tiles + t], 0, 0)

    def lat_index(t, w, wk, pg, kl, ly):
        return (ly[0],) + page_index(t, w, wk, pg)

    in_specs = [pl.BlockSpec((None, H, Dl), row_index)] + [
        pl.BlockSpec((None, None, PS, Dl), functools.partial(lat_index, t))
        for t in range(tiles)]
    operands = (q,) + (lat,) * tiles
    routine = _PAGE_ROUTINES[work.routine]
    if ls is not None:
        in_specs.append(pl.BlockSpec((None, 1, PS),
                                     functools.partial(page_index, 0)))
        operands += (ls,)
        routine = _latent_page_int8
    prefetch = (work.work, pages, kv_lens, layer)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(work.n_work,),  # a traced bound: the rows' live steps
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, H, dc), row_index),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dc), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, tiles=tiles, quantized=ls is not None,
            page_size=PS * tiles, max_pages=steps, scale=scale,
            routine=routine),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dc), q.dtype),
        interpret=interpret,
        name="decode_mla_attention",
    )(*prefetch, *operands)
    # a row with no live page is never visited, so its output block is
    # never written: define it, as 0 (decode_paged_attention's rule)
    return jnp.where((kv_lens > 0)[:, None, None], out, 0)


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
    work=None,  # latent_walk's `Walk` at a shard's heads, replicated
    *,
    dc: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel wrapper: per-head independence means each shard
    runs the kernel on its local heads against the replicated latent pool
    — zero collectives (the block all-reduce happens in the
    out-projection as usual). The walk is the same on every shard: built
    once, outside, and it rides in replicated
    (`decode_paged_attention_sharded`'s way)."""
    from jax.sharding import PartitionSpec as P

    lat_pool, _, layer = stacked_pools(lat_pool, None, layer)
    if work is None:
        work = latent_walk(q.shape[1] // mesh.shape[axis_name], lat_pool,
                           page_table, kv_lens)
    fn = jax.shard_map(
        functools.partial(
            decode_mla_attention, dc=dc, scale=scale, interpret=interpret
        ),
        mesh=mesh,
        in_specs=(P(None, axis_name, None), SPEC_MLA_LATENT_POOL,
                  P(None, None), P(None), P(), P()),
        out_specs=P(None, axis_name, None),
        check_vma=False,
    )
    return fn(q, lat_pool, page_table, kv_lens, layer, work)
