"""Pallas TPU ragged paged attention: one walk for decode + packed prefills.

The mixed iteration's hot op. PR 1's token-budget scheduler packs the decode
batch plus several partial-prefill chunks into one fused dispatch, but the
device path pads them into a dense [N, S] batch: a pack of one 512-token
chunk and three 32-token chunks pays 4x512 tokens of attention+MLP, and the
runner compiles a variant per (decode, chunk, pack) bucket triple. This
kernel serves every segment — each decode sequence is a q_len=1 segment,
each prefill chunk a q_len=n segment — from ONE flat [T, Hk, G, D] query
buffer whose length T comes from a small set of token-budget buckets, so
mixed-iteration cost is proportional to real tokens and the compile key is
T alone.

Work units. The flat token axis is cut into q_block-row blocks; a block
that spans a segment boundary would mix two segments' (page table, kv_len,
positions), so the host emits one WORK UNIT per (block, segment) overlap:

    meta [5, NW] int32 rows:           (scalar-prefetched, SMEM)
      0 seg    segment row into seg_page_table / seg_kv_lens
      1 qblk   flat q block index (block of q_block tokens)
      2 rs     first valid row of this unit within the block
      3 rows   valid row count (0 = padding unit, a no-op)
      4 qpos0  absolute position of row rs

NW and the segment capacity are functions of the T bucket only
(`ragged_work_cap` / `ragged_seg_cap`), so they never add compile keys.

Grid: a WORK LIST of live (work unit, page) pairs, one grid step each, its
length a traced bound, as in the decode kernel (ops/paged_attention.py).
A call costs what its units can see, not units x the page table's width
(under the old grid (NW, MP) the serving cell's mixed step took 8,512
steps a layer for 100-230 live pairs). Unit w sees pages first .. last:
`last` the page of min(qpos0 + rows - 1, kv_len - 1), `first` the page of
max(qpos0 - window + 1, 0) under a sliding window and 0 without one
(`_unit_pages`, which is `live_pages`, the decode walk's rule, for a run
of `rows` queries: a decode row is its one-query case). A padding unit
(rows 0) and the dummy tail (kv_len 0) see none and are never visited.
`ragged_work_list` builds the list in XLA from `meta`, the segments'
lengths and the window: entry g is `unit * MP + page`, units in `meta`'s
order and each unit's pages ascending, NW * MP entries of capacity (a
function of the T bucket and MP alone; int32 in SMEM beside the segment
page table: 140 KB + 99 KB at the worker's defaults, T 320 and MP 256;
the chip's 1 MiB holds T 320 up to MP 1024 and T 2048 up to MP 512). In
XLA and not on the host: nothing more to stage or send a dispatch, and a
model whose layers alternate sliding and global windows needs two lists.
A caller that runs many layers on one plan builds it once
(models/llama.py, above its layer scan). The kernel body reads the same
`_unit_pages` from SMEM scalars: the softmax state is initialised at a
unit's first page and written out at its last. Consecutive entries of
one q block keep the q and out blocks resident (same block index ->
Pallas elides the DMA), and each unit read-modify-writes ONLY its rows of
the out block under a row mask at finalize. Units are emitted in
increasing-row order so a later unit never clobbers an earlier one's
rows. A row that no visited unit covers is never written; the wrapper
defines it as 0 from the list's `covered` mask. K/V pages stream exactly
as in the decode kernel, from the layer-stacked pool [L, NP, PS, Hk, D]
at a scalar-prefetched layer: the index maps read the list, then the
unit's segment, then its page-table row; no page is clamped, because no
dead page is in the list.

Parity: GQA (G groups per kv head), sliding window (traced scalar, 0 =
global at runtime), logit softcap, and int8-KV per-(token, head) scales all
follow the exact op order of the two kernels this subsumes — scales fold
into scores BEFORE softcap, V scales fold into p AFTER the raw-probability
denominator.

The flat layout itself is the "Ragged Paged Attention" TPU kernel design
(PAPERS.md); the reference framework reaches the same shape through
vLLM's ragged query batch on GPU.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import (
    _div, _rem, live_pages, scalar_operands, split_scales, stacked_pools,
    work_list,
)
from dynamo_tpu.parallel.mesh import AXIS_MODEL, attention_specs

NEG_INF = -1e30

# decode batch (<=64) + packed chunks (<=32) in one mixed iteration
RAGGED_MAX_SEGS = 96
DEFAULT_Q_BLOCK = 8


def ragged_seg_cap(t_bucket: int, max_segs: int = RAGGED_MAX_SEGS) -> int:
    """Segment-row capacity for a T bucket (+1 for the padding-tail
    segment). A function of the bucket ONLY — it must not add compile
    keys beyond |T buckets|."""
    return min(t_bucket, max_segs) + 1


def ragged_work_cap(
    t_bucket: int,
    q_block: int = DEFAULT_Q_BLOCK,
    max_segs: int = RAGGED_MAX_SEGS,
) -> int:
    """Work-unit capacity: every block yields one unit plus one extra per
    segment that starts mid-block, so blocks + segments bounds it."""
    if t_bucket % q_block:
        raise ValueError(f"t_bucket {t_bucket} not a multiple of {q_block}")
    return t_bucket // q_block + ragged_seg_cap(t_bucket, max_segs)


def build_ragged_metadata(
    q_lens: Sequence[int],  # true (unpadded) query tokens per segment
    q_starts: Sequence[int],  # absolute position of each segment's token 0
    kv_lens: Sequence[int],  # context length per segment (incl. its chunk)
    page_rows: Sequence[Sequence[int]],  # page-table row per segment
    t_bucket: int,
    *,
    q_block: int = DEFAULT_Q_BLOCK,
    max_pages: Optional[int] = None,
    max_segs: int = RAGGED_MAX_SEGS,
) -> Dict[str, np.ndarray]:
    """Host-side (numpy) metadata for one ragged dispatch.

    Segments are laid out back to back in the flat [t_bucket] token axis in
    the given order; the tail [sum(q_lens), t_bucket) is covered by a dummy
    segment with kv_len=0 (no live page: the kernel never visits it and
    its wrapper returns 0 there). Returns the
    kernel operands (seg_page_table, seg_kv_lens, meta) padded to the
    bucket's static caps, plus per-token arrays for the model's KV writes /
    RoPE / jnp fallback (tok_*) and the per-segment last-token gather
    (last_index). Padding tokens get tok_pos=-1 (KV write drops them) but
    tok_kv_len=1 so the jnp fallback's softmax stays finite.

    Segments are fully independent — each brings its own page-table row
    and kv_len — which is what lets speculative verify treat tree
    branches as ordinary extra segments: a branch rides the dispatch on
    its forked table (trunk pages shared by reference, divergent tail
    copied), and this metadata neither knows nor cares that two
    segments' rows alias the same physical pages.
    """
    n = len(q_lens)
    t_real = int(sum(q_lens))
    if t_real > t_bucket:
        raise ValueError(f"{t_real} tokens exceed bucket {t_bucket}")
    if n > max_segs:
        raise ValueError(f"{n} segments exceed cap {max_segs}")
    seg_cap = ragged_seg_cap(t_bucket, max_segs)
    nw = ragged_work_cap(t_bucket, q_block, max_segs)
    if max_pages is None:
        max_pages = max((len(r) for r in page_rows), default=1)

    seg_pt = np.zeros((seg_cap, max_pages), np.int32)
    seg_kvl = np.zeros((seg_cap,), np.int32)
    for s, row in enumerate(page_rows):
        seg_pt[s, : len(row)] = row
    seg_kvl[:n] = kv_lens

    # flat extents per segment, dummy tail included
    lens_all: List[int] = list(int(x) for x in q_lens)
    if t_real < t_bucket:
        lens_all.append(t_bucket - t_real)
    meta = np.zeros((5, nw), np.int32)
    w = 0
    lo = 0
    for s, ln in enumerate(lens_all):
        hi = lo + ln
        for b in range(lo // q_block, (hi - 1) // q_block + 1):
            blo = max(lo, b * q_block)
            bhi = min(hi, (b + 1) * q_block)
            qp0 = int(q_starts[s]) + (blo - lo) if s < n else 0
            meta[:, w] = (s, b, blo - b * q_block, bhi - blo, qp0)
            w += 1
        lo = hi
    # padding units: rows=0, no live page, never visited. They point at
    # the last real block and a real segment row all the same: the walk's
    # entries past its bound name them, and an index map may read those
    if w:
        pad_blk = meta[1, w - 1]
    else:
        pad_blk = 0
    pad_seg = min(n, seg_cap - 1)
    for j in range(w, nw):
        meta[:, j] = (pad_seg, pad_blk, 0, 0, 0)

    tok_pt = np.zeros((t_bucket, max_pages), np.int32)
    tok_kvl = np.ones((t_bucket,), np.int32)
    tok_pos = np.full((t_bucket,), -1, np.int32)
    cu = np.zeros((n + 1,), np.int32)
    off = 0
    for s in range(n):
        ln = int(q_lens[s])
        tok_pt[off : off + ln] = seg_pt[s]
        tok_kvl[off : off + ln] = kv_lens[s]
        tok_pos[off : off + ln] = int(q_starts[s]) + np.arange(ln)
        off += ln
        cu[s + 1] = off
    return {
        "seg_page_table": seg_pt,
        "seg_kv_lens": seg_kvl,
        "meta": meta,
        "tok_page_table": tok_pt,
        "tok_kv_lens": tok_kvl,
        "tok_positions": tok_pos,
        "cu_q_lens": cu,
        "last_index": (cu[1:] - 1).astype(np.int32),
        "n_work": np.int32(w),
    }


def ragged_work_list(meta, seg_kv_lens, window, page_size: int,
                     max_pages: int, n_tokens: int,
                     q_block: int = DEFAULT_Q_BLOCK):
    """The ragged kernel's walk, built in XLA from the work units, the
    segments' lengths and the window: (work[W] int32, n_work int32,
    covered[T] bool).

    Entry g < n_work is `unit * MP + page` of the g-th live (unit, page)
    pair: units in `meta`'s order (increasing rows), each unit's pages
    ascending from `_unit_pages`' first to its last. A padding unit
    (rows 0) and the dummy tail (kv_len 0) bring none. W = NW * MP is the
    static bound, a function of the T bucket and the page table's width
    alone. `covered` marks the flat rows that some visited unit writes;
    the kernel's wrapper zeroes the rest. A caller that runs many layers
    on one plan builds this once (models/llama.py, above its layer
    scan)."""
    seg, qblk, rs, rows, qpos0 = meta
    first, last, live = _unit_pages(
        rows, qpos0, seg_kv_lens[seg], window, page_size, max_pages)
    work, n_work = work_list(
        first, jnp.where(live, last - first + 1, 0), max_pages)
    lo = qblk * q_block + rs
    t = lax.iota(jnp.int32, n_tokens)[:, None]
    covered = jnp.any(live & (t >= lo) & (t < lo + rows), axis=1)
    return work, n_work, covered


def ragged_live_pairs(meta, seg_kv_lens, window: int, page_size: int,
                      max_pages: int) -> int:
    """`ragged_work_list`'s n_work on the host, in numpy, from the
    metadata `build_ragged_metadata` returned (`window` 0: none): the
    count behind `IterationRecord.ragged_pages_live`, with no device
    work. The rule is `_unit_pages`'; tests hold the two together."""
    seg, _, _, rows, qpos0 = np.asarray(meta)
    kv = np.asarray(seg_kv_lens)[seg]
    last = np.minimum(
        np.maximum(np.minimum(qpos0 + rows - 1, kv - 1), 0) // page_size,
        max_pages - 1)
    lo = np.maximum(qpos0 - window + 1, 0) if window > 0 else 0
    first = np.minimum(lo // page_size, last)
    return int(np.sum(np.where((rows > 0) & (kv > 0), last - first + 1, 0)))


def _unit_pages(rows, qpos0, kv_len, window, page_size: int, max_pages: int):
    """(first, last, live) of a work unit: `rows` queries of one segment
    from position qpos0 on see the pages `live_pages` gives their run;
    a unit without rows, or of a segment without tokens, sees none.
    The one rule that the list (arrays) and the kernel body (SMEM
    scalars) both read."""
    first, last = live_pages(
        qpos0, qpos0 + rows - 1, kv_len, window, page_size, max_pages)
    return first, last, (rows > 0) & (kv_len > 0)


def _ragged_kernel_body(
    # scalar prefetch
    work_ref,  # [NW * MP] int32: unit * MP + page of each live pair
    meta_ref,  # [5, NW] int32 (seg, qblk, rs, rows, qpos0)
    pt_ref,  # [SEG, MP] int32 per-segment page-table rows
    kvl_ref,  # [SEG] int32 per-segment context length
    #   (the pool's layer [1] rides next; only the index maps read it)
    win_ref,  # [1] int32 sliding window (0 = global) or None
    # blocks (at one KV head, dense: the head axis is gone from all of
    # them, the page the contiguous [PS, D] tile it is in the pool)
    q_ref,  # [Hk, QB*G, D] (row r is block token r // G, group r % G)
    k_ref,  # [PS, Hk, D] one token-major page
    v_ref,  # [PS, Hk, D]
    ks_ref,  # [PS, Hk] f32 per-vector K scales (int8 KV) or None
    vs_ref,  # [PS, Hk] f32 per-vector V scales or None
    o_ref,  # [Hk, QB*G, D]
    # scratch (persist across a unit's pages)
    m_ref,  # [Hk, QB*G, 1] f32
    l_ref,  # [Hk, QB*G, 1] f32
    acc_ref,  # [Hk, QB*G, D] f32
    *,
    page_size: int,
    max_pages: int,
    n_groups: int,
    scale: float,
    softcap: float = 0.0,
):
    entry = work_ref[pl.program_id(0)]
    w = _div(entry, max_pages)
    i = _rem(entry, max_pages)
    seg = meta_ref[0, w]
    row_start = meta_ref[2, w]
    n_rows = meta_ref[3, w]
    qpos0 = meta_ref[4, w]
    kv_len = kvl_ref[seg]
    wv = None if win_ref is None else win_ref[0]
    first, last, _ = _unit_pages(
        n_rows, qpos0, kv_len, wv, page_size, max_pages)
    page_first = i * page_size

    @pl.when(i == first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every pair the walk visits is live: the page runs unguarded
    q = q_ref[...].astype(jnp.float32)  # [Hk, QB*G, D]
    k = k_ref[...].astype(jnp.float32)  # [PS, Hk, D]
    one_head = k.ndim == 2  # two plain products, [QB*G, D] x [PS, D]
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())) if one_head
        else (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32
    ) * scale  # [Hk, QB*G, PS]
    if ks_ref is not None:
        s = s * ks_ref[...].T[:, None, :]
    if softcap:
        # the TRUE score (post any int8 fold), matching the jnp path
        s = softcap * jnp.tanh(s / softcap)

    row = _div(lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 2), n_groups)
    col = lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    q_pos = qpos0 + row - row_start  # valid only inside the row band
    kv_pos = page_first + col
    mask = (
        (row >= row_start)
        & (row < row_start + n_rows)
        & (kv_pos <= q_pos)
        & (kv_pos < kv_len)
    )
    if wv is not None:
        mask = mask & ((wv <= 0) | (kv_pos > q_pos - wv))
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)

    l_add = jnp.sum(p, axis=-1, keepdims=True)  # raw-probability denom
    if vs_ref is not None:
        p = p * vs_ref[...].T[:, None, :]
    v = v_ref[...].astype(jnp.float32)
    pv = lax.dot_general(
        p, v, (((1,), (0,)), ((), ())) if one_head
        else (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    l_ref[...] = l_ref[...] * alpha + l_add
    m_ref[...] = m_new

    @pl.when(i == last)
    def _finalize():
        # read-modify-write ONLY this unit's row band: units sharing the
        # block run back to back on the same resident out buffer, each
        # masking in its own rows (increasing-row emission order). What
        # the buffer held outside every band of the block is whatever
        # was there: the wrapper's `covered` mask defines those rows
        denom = jnp.maximum(l_ref[...], 1e-30)
        res = acc_ref[...] / denom  # [Hk, QB*G, D]
        row = _div(lax.broadcasted_iota(jnp.int32, res.shape, res.ndim - 2),
                   n_groups)
        keep = (row >= row_start) & (row < row_start + n_rows)
        prev = o_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.where(keep, res, prev).astype(o_ref.dtype)


def _ragged_kernel(wk, meta, pt, kl, ly, q, k, v, o, m, l, acc, **kw):
    _ragged_kernel_body(wk, meta, pt, kl, None, q, k, v, None, None,
                        o, m, l, acc, **kw)


def _ragged_kernel_win(wk, meta, pt, kl, ly, win, q, k, v, o, m, l, acc,
                       **kw):
    _ragged_kernel_body(wk, meta, pt, kl, win, q, k, v, None, None,
                        o, m, l, acc, **kw)


def _ragged_kernel_int8(wk, meta, pt, kl, ly, q, k, ks, v, vs, o, m, l, acc,
                        **kw):
    _ragged_kernel_body(wk, meta, pt, kl, None, q, k, v, ks, vs,
                        o, m, l, acc, **kw)


def _ragged_kernel_int8_win(wk, meta, pt, kl, ly, win, q, k, ks, v, vs, o, m,
                            l, acc, **kw):
    _ragged_kernel_body(wk, meta, pt, kl, win, q, k, v, ks, vs,
                        o, m, l, acc, **kw)


def ragged_attention_reference(
    q: jax.Array,  # [T, Hk, G, D]
    k_pool_l,
    v_pool_l,
    tok_page_table: jax.Array,  # [T, MP]
    tok_positions: jax.Array,  # [T] (-1 = padding)
    tok_kv_lens: jax.Array,  # [T]
    *,
    scale=None,
    softcap: float = 0.0,
    window=None,
) -> jax.Array:
    """jnp reference (and CPU fallback): each flat token is a B=T, S=1 row
    of the canonical paged_attention_jnp — per-token page table / kv_len /
    position make arbitrary segment layouts exactly correct."""
    from ..models.toolkit import paged_attention_jnp

    out = paged_attention_jnp(
        q[:, None],
        k_pool_l,
        v_pool_l,
        tok_page_table,
        jnp.maximum(tok_positions, 0)[:, None],
        tok_kv_lens,
        scale=scale,
        softcap=softcap,
        window=window,
    )
    return out[:, 0]


def ragged_paged_attention_sharded(
    q: jax.Array,  # [T, Hk, G, D] heads sharded over `axis_name`
    k_pool,  # [L, NP, PS, Hk, D] heads sharded over `axis_name`
    v_pool,
    seg_page_table: jax.Array,
    seg_kv_lens: jax.Array,
    meta: jax.Array,
    mesh,
    axis_name: str = AXIS_MODEL,
    window=None,
    layer=None,  # traced int32 scalar, replicated
    work=None,  # ragged_work_list's triple, replicated (see below)
    *,
    q_block: int = DEFAULT_Q_BLOCK,
    scale=None,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel wrapper (see decode_paged_attention_sharded): each
    model-axis shard runs the kernel over its local kv-heads."""
    from jax.sharding import PartitionSpec as P

    heads, pool, scales = attention_specs(axis_name)
    if isinstance(k_pool, dict):  # int8 KV: scales [L, NP, PS, Hk]
        pool = {"q": pool, "s": scales}
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    scalars = scalar_operands(layer, window)
    if work is None:  # the same on every shard: built once, outside
        PS = jax.tree.leaves(k_pool)[0].shape[2]
        work = ragged_work_list(meta, seg_kv_lens, window, PS,
                                seg_page_table.shape[1], q.shape[0], q_block)

    def part(q, k_pool, v_pool, seg_pt, seg_kvl, meta, work, n_work, covered,
             layer, window=None):
        return ragged_paged_attention(
            q, k_pool, v_pool, seg_pt, seg_kvl, meta, window, layer,
            (work, n_work, covered), q_block=q_block, scale=scale,
            softcap=softcap, interpret=interpret,
        )

    fn = jax.shard_map(
        part, mesh=mesh,
        in_specs=(heads, pool, pool, P(None, None), P(None), P(None, None),
                  P(None), P(), P(None)) + (P(),) * len(scalars),
        out_specs=heads, check_vma=False,
    )
    return fn(q, k_pool, v_pool, seg_page_table, seg_kv_lens, meta, *work,
              *scalars)


@functools.partial(
    jax.jit, static_argnames=("q_block", "interpret", "scale", "softcap")
)
def ragged_paged_attention(
    q: jax.Array,  # [T, Hk, G, D] flat query tokens (all segments)
    k_pool,  # [L, NP, PS, Hk, D] stacked token-major pool (or int8
    #   {"q","s"} dict; or one layer's [NP, PS, Hk, D]: stacked_pools)
    v_pool,
    seg_page_table: jax.Array,  # [SEG, MP] int32
    seg_kv_lens: jax.Array,  # [SEG] int32
    meta: jax.Array,  # [5, NW] int32 work units (build_ragged_metadata)
    window=None,  # None = no-window compile; else traced int32 scalar
    layer=None,  # traced int32 scalar: the stacked pool's layer to read
    work=None,  # ragged_work_list(meta, seg_kv_lens, window, PS, MP, T,
    #   q_block), for a caller that runs many layers on one plan and
    #   builds it once; None = built here
    *,
    q_block: int = DEFAULT_Q_BLOCK,
    scale=None,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jax.Array:
    """Returns [T, Hk, G, D]; rows covered by no real segment return 0.
    Every segment's K/V (including its own chunk) must already be written
    to the pool. The compile key is (T, NW, SEG, q_block) — all functions
    of the T bucket, so variants stay at |T buckets|."""
    T, Hk, G, D = q.shape
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    kq, vq, ks, vs = split_scales(k_pool, v_pool, layer)
    quantized = ks is not None
    _, NP, PS, _, _ = kq.shape
    MP = seg_page_table.shape[1]
    if T % q_block:
        raise ValueError(f"T {T} not a multiple of q_block {q_block}")
    if scale is None:
        scale = D**-0.5
    windowed = window is not None
    if windowed:
        window = jnp.asarray(window, jnp.int32).reshape(())
    work, n_work, covered = work or ragged_work_list(
        meta, seg_kv_lens, window, PS, MP, T, q_block)

    # group axis merged into the rows HERE, in XLA: a [.., G, D] block
    # pads G up to a full sublane tile in VMEM and Mosaic cannot
    # shape-cast every (QB, G) split (G == 1 fails to lower)
    qt = q.transpose(1, 0, 2, 3).reshape(Hk, T * G, D)
    # one KV head, dense (ops/paged_attention.py "One KV head"): the pool
    # as the step program carries it, [L, NP, PS, D], a page one contiguous
    # [PS, D] tile, and the head axis dropped from every block
    one_head = Hk == 1 and not quantized
    heads = () if one_head else (Hk,)
    if one_head:
        qt = qt.reshape(T * G, D)
        kq, vq = (p.reshape(p.shape[:3] + (D,)) for p in (kq, vq))

    # the index maps read the list, then the unit, then the page table:
    # entry g is `unit * MP + page` of a live pair, so no page is clamped
    def kv_index(g, wk, mt, pt, kl, ly, *rest):
        return (ly[0], pt[mt[0, _div(wk[g], MP)], _rem(wk[g], MP)]
                ) + (0,) * (kq.ndim - 2)

    def scale_index(g, wk, mt, pt, kl, ly, *rest):
        return kv_index(g, wk, mt, pt, kl, ly, *rest)[1:4]

    def q_index(g, wk, mt, *rest):
        return (0,) * len(heads) + (mt[1, _div(wk[g], MP)], 0)

    q_spec = pl.BlockSpec(heads + (q_block * G, D), q_index)
    # one token-major page of one layer = one contiguous PS*Hk*D slab
    # (single DMA)
    kv_spec = pl.BlockSpec((None, None, PS) + heads + (D,), kv_index)
    kw = dict(page_size=PS, max_pages=MP, n_groups=G, scale=scale,
              softcap=softcap)
    if quantized:
        kernel = functools.partial(
            _ragged_kernel_int8_win if windowed else _ragged_kernel_int8,
            **kw,
        )
        s_spec = pl.BlockSpec((None, PS, Hk), scale_index)
        in_specs = [q_spec, kv_spec, s_spec, kv_spec, s_spec]
        operands = (qt, kq, ks, vq, vs)
    else:
        kernel = functools.partial(
            _ragged_kernel_win if windowed else _ragged_kernel, **kw
        )
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (qt, kq, vq)

    prefetch = (work, meta, seg_page_table, seg_kv_lens) + scalar_operands(
        layer, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # work, meta, seg_pt, seg_kvl,
        #   layer (+ window)
        grid=(n_work,),  # a traced bound: the live pairs, not NW * MP
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM(heads + (q_block * G, 1), jnp.float32),
            pltpu.VMEM(heads + (q_block * G, 1), jnp.float32),
            pltpu.VMEM(heads + (q_block * G, D), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(*prefetch, *operands)
    # [Hk, T*G, D] -> [T, Hk, G, D]; a row that no visited unit covers
    # (bucket padding, a segment without tokens) was never written:
    # define it, as 0
    out = out.reshape(Hk, T, G, D).transpose(1, 0, 2, 3)
    return jnp.where(covered[:, None, None, None], out, 0)
