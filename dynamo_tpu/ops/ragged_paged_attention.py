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

What a step does with its pages is the decode kernel's choice of routines
(ops/paged_attention.py `_PAGE_ROUTINES`, its module docstring says why no
head is ever brought together), asked in one place from the static shapes
a call sees (`ragged_page_routine`, a rule of this kernel's own shapes),
under one body. An int8 pool takes `by_heads`: one page a step, a batched
float32 product a KV head, the scales folded in per (token, head); so
does G = 1 at 32 KV heads or more (phi-3), the one dense geometry it
measured faster at. Every other dense pool takes `by_tiles`: the q block
is ONE matrix [Hk * G * q_block, D], rows (head, group, token),
against the step's pages as they lie in the pool, in the pool's dtype,
columns of other KV heads masked; where a decode row has one pair of
bounds for its page, here each row has its own (its position inside the
unit's band, the segment's length, the window), and the routine takes
them as a column. A step brings `ragged_step_tiles` pages of the unit
(`step_tiles`' count by the page's bytes, halved while the score block,
q_block * Hk * G rows by tiles * PS * Hk columns of float32, passes
SCORE_BYTES), each a block of its own on the same operand, and the walk is
the same walk at that granularity: `ragged_walk` lists (unit, step) pairs
and hands the kernel the segments' page table with every entry outside a
segment's live run replaced by the run's nearest end, so a tile past the
unit's pages names a live page of its segment and is masked by position.
The walk is a `Walk` (ops/paged_attention.py): it carries the routine and
the pages a step beside its lists, decided from the heads ONE call sees (a
tensor-parallel shard's, though the walk is built outside `shard_map` on
pools that still have every head), and the call reads them off it.
One KV head is the case with nothing to mask, on the 4-d view [L, NP, PS,
D] of the stack. On a v5e at mimo-v2-flash's two kinds of layer the
float32 product a head cost 6.2 and 6.7 us a live pair where this costs
1.6 and 3.3 (PERF.md section 6, PR 46).

Parity: GQA (G groups per kv head), sliding window (traced scalar, 0 =
global at runtime), logit softcap, a sink, and int8-KV per-(token, head)
scales all follow the exact op order of the two kernels this subsumes —
scales fold into scores BEFORE softcap, V scales fold into p AFTER the
raw-probability denominator.

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
    _PAGE_ROUTINES, Walk, _div, _rem, _window_lo, filled_page_table,
    live_pages, page_bytes, scalar_operands, split_scales, stacked_pools,
    step_tiles, work_list,
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


def ragged_page_routine(Hk: int, G: int, quantized: bool) -> str:
    """What a grid step of the ragged kernel does with its pages, THE
    decision, from the static shapes one call sees (a tensor-parallel
    shard: its local heads), stated on the ragged kernel's own shapes and
    measurements, in this order. "by_heads", one page a step and a float32
    product a KV head: an int8 pool, whose scales ride per (token, head);
    and G = 1 at RAGGED_MHA_HEADS KV heads or more, where the tile routine
    loses: it multiplies every query row by every (token, head) column, Hk
    times the useful products on the MXU and Hk times the score block on
    the VPU, quadratic in Hk where a product a head is linear, and at G = 1
    the rows are q_block * Hk with nothing of a group to fill them.
    "by_tiles" for every other dense pool: GQA at any Hk and G, one KV
    head (nothing to mask), G = 1 at fewer heads, with or without a sink
    or values narrower than keys, whatever the pool's dtype (the decode
    kernel's `page_routine` hangs on those; nothing of this rule does):
    the q block's rows against the step's pages as they lie in the pool
    (`_pages_by_tiles`). `ragged_walk` asks
    here and the call reads the answer off the walk;
    `ModelRunner.device_report` asks here too."""
    if quantized or (G == 1 and Hk >= RAGGED_MHA_HEADS):
        return "by_heads"
    return "by_tiles"


# KV heads from which a G = 1 (MHA) call keeps the float32 product a head.
# us a live pair on the cell's mixed step, by heads / by tiles, at D 128
# (my chip run, PR 46, `scripts/bench_attn.py --ragged --routines`): Hk 4
# 2.89 / 0.39, Hk 8 2.92 / 0.50, Hk 16 3.47 / 1.26, Hk 32 4.18 / 4.30; phi-3
# (Hk 32, D 96) 3.35 / 4.27
RAGGED_MHA_HEADS = 32


# float32 bytes of the score block a step of the tile routine may hold:
# `rows` x the step's (token, head) columns. The decode kernel's rows are
# one token's heads and its step is bounded by the pages' bytes alone
# (STEP_BYTES); a q block brings q_block times the rows, and the scores,
# the probabilities and their three bf16 terms are each a block this size
# in VMEM and on the VPU
SCORE_BYTES = 1 << 20


def ragged_step_tiles(rows: int, page_cols: int, nbytes: int,
                      max_pages: int) -> int:
    """Pages a step of the ragged tile routine brings: `step_tiles`' count
    from the page's bytes, halved while the step's score block, `rows`
    (q_block * Hk * G) x `page_cols` (PS * Hk) a page of float32, passes
    SCORE_BYTES. One page at least, whatever the rows."""
    tiles = step_tiles(nbytes, max_pages)
    while tiles > 1 and rows * page_cols * tiles * 4 > SCORE_BYTES:
        tiles //= 2
    return tiles


def _routine_and_tiles(heads, kq, vq, quantized: bool, max_pages: int,
                       q_block: int):
    """(routine, pages a step) of one ragged call, from the heads IT sees
    (`heads`; the pools give the page's tokens, the dtype and the widths
    alone: their head axis is every shard's heads where a walk is built)."""
    Hk, G = heads
    PS = kq.shape[-3]
    routine = ragged_page_routine(Hk, G, quantized)
    if routine != "by_tiles":
        return routine, 1
    return routine, ragged_step_tiles(
        q_block * Hk * G, PS * Hk, page_bytes(Hk, kq, vq), max_pages)


def ragged_walk(heads, k_pool, v_pool, seg_page_table, seg_kv_lens, meta,
                window, n_tokens: int,
                q_block: int = DEFAULT_Q_BLOCK) -> Walk:
    """The `Walk` of a ragged call, for a caller that runs many layers on
    one plan and builds it once (`decode_walk`'s twin). `heads` = (Hk, G)
    of ONE call (a shard's local heads); with the pools' page size, dtype
    and widths they decide the routine and the pages a step
    HERE (`_routine_and_tiles`), and the call takes both from the walk: it
    never decides again. "by_heads": `ragged_work_list`'s triple.
    "by_tiles": the same walk over steps of `tiles` pages, entry g = `unit
    * steps_a_unit + step`, and `pages` the segments' page table,
    flattened, with every entry outside a segment's live run (the pages
    its units see, first to last) replaced by the run's nearest end
    (`filled_page_table`): the t-th tile of a step is `pages[seg * MP +
    step * tiles + t]`. A tile past either end of the UNIT's pages is thus
    another live page of its segment, or a repeat of one, its slots masked
    by position: the kernel reads no dead page-table entry and no dead
    page."""
    quantized = isinstance(k_pool, dict)
    kq, vq = (p["q"] if quantized else p for p in (k_pool, v_pool))
    PS, MP = kq.shape[-3], seg_page_table.shape[1]
    routine, tiles = _routine_and_tiles(heads, kq, vq, quantized, MP, q_block)
    work, n_work, covered = ragged_work_list(
        meta, seg_kv_lens, window, PS * tiles, MP // tiles, n_tokens, q_block)
    if routine != "by_tiles":
        return Walk(work, n_work, covered, None, routine, tiles)
    seg, _, _, rows, qpos0 = meta
    first, last, live = _unit_pages(
        rows, qpos0, seg_kv_lens[seg], window, PS, MP)
    mine = live[None, :] & (
        seg[None, :] == lax.iota(jnp.int32, seg_page_table.shape[0])[:, None])
    pages = filled_page_table(
        seg_page_table,
        jnp.min(jnp.where(mine, first[None, :], MP), axis=1),
        jnp.max(jnp.where(mine, last[None, :], -1), axis=1))
    return Walk(work, n_work, covered, pages, routine, tiles)


def _ragged_kernel_body(
    # scalar prefetch
    work_ref,  # [NW * steps] int32: unit * steps + step of each live pair
    meta_ref,  # [5, NW] int32 (seg, qblk, rs, rows, qpos0)
    pt_ref,  # [SEG, MP] int32 per-segment page-table rows (by tiles: the
    #   walk's filled-in table, flat; only the index maps read either)
    kvl_ref,  # [SEG] int32 per-segment context length
    #   (the pool's layer [1] rides next; only the index maps read it)
    win_ref,  # [1] int32 sliding window (0 = global) or None
    # blocks, by heads / by tiles (ops/paged_attention.py `_PAGE_ROUTINES`)
    q_ref,  # [Hk, QB*G, D] rows (token, group) / [Hk*G*QB, D] rows (head,
    #   group, token) ([QB*G, D] rows (token, group) at one KV head)
    k_ref,  # [PS, Hk, D] one token-major page / the step's pages, a tuple
    #   of such blocks ([PS, D] tiles of the 4-d view at one head)
    v_ref,  # like k_ref, at the values' width Dv
    ks_ref,  # [PS, Hk] f32 per-vector K scales (int8 KV) or None
    vs_ref,  # [PS, Hk] f32 per-vector V scales or None
    o_ref,  # like q_ref, Dv wide
    # scratch (persist across a unit's pages)
    m_ref,  # f32 [Hk, QB*G, 1] / [Hk*G*QB, 1]
    l_ref,  # like m_ref
    acc_ref,  # f32 like o_ref
    *,
    page_size: int,  # tokens a grid step covers: a page (by tiles: its
    #   tiles' pages together, and max_pages the steps a unit can take)
    max_pages: int,
    n_groups: int,
    q_block: int,
    scale: float,
    softcap: float = 0.0,
    routine: str = "by_heads",  # ragged_page_routine
    token_minor: bool = False,  # the rows' order: (head, group, token), or
    #   (token, group) of one head
    sink_ref=None,  # f32 like m_ref: the sink logit of each row's query head
    #   (ops/paged_attention.py; None: no sink)
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

    @pl.when(i == first)
    def _init():
        if sink_ref is None:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        else:  # the sink seeds the running softmax: a score with no value
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every pair the walk visits is live, so the step runs unguarded. What
    # a row may see of it is the row's own: the block's token of each row,
    # its position inside the unit's band, and from them the slots of the
    # step below min(q_pos + 1, kv_len) and from the window's edge on; a
    # row outside the band sees none
    rows = lax.broadcasted_iota(jnp.int32, m_ref.shape, m_ref.ndim - 2)
    if token_minor:
        tok = (rows & (q_block - 1) if q_block & (q_block - 1) == 0
               else _rem(rows, q_block))
    else:
        tok = _div(rows, n_groups)
    in_band = (tok >= row_start) & (tok < row_start + n_rows)
    q_pos = qpos0 + tok - row_start
    n_valid = jnp.where(
        in_band, jnp.minimum(q_pos + 1, kv_len) - i * page_size, 0)
    lo_in_step = _window_lo(q_pos, wv) - i * page_size
    _PAGE_ROUTINES[routine](
        q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref, acc_ref,
        n_valid, lo_in_step, scale=scale, softcap=softcap)

    @pl.when(i == last)
    def _finalize():
        # read-modify-write ONLY this unit's row band: units sharing the
        # block run back to back on the same resident out buffer, each
        # masking in its own rows (increasing-row emission order). What
        # the buffer held outside every band of the block is whatever
        # was there: the wrapper's `covered` mask defines those rows
        denom = jnp.maximum(l_ref[...], 1e-30)
        res = acc_ref[...] / denom
        prev = o_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.where(in_band, res, prev).astype(o_ref.dtype)


def _ragged_kernel(wk, meta, pt, kl, ly, *refs, tiles, windowed, sinked,
                   quantized, **kw):
    """One signature for every call: a window operand first where there is
    one, q, `tiles` refs each of K and V (an int8 pool: the page and its
    scales), a sink operand behind them where there is one."""
    win, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    q, refs = refs[0], refs[1:]
    if quantized:
        k, ks, v, vs, *rest = refs
    else:
        ks = vs = None
        k, v, rest = refs[:tiles], refs[tiles:2 * tiles], refs[2 * tiles:]
        if kw["routine"] == "by_heads":
            (k,), (v,) = k, v
    sink, rest = (rest[0], rest[1:]) if sinked else (None, rest)
    _ragged_kernel_body(wk, meta, pt, kl, win, q, k, v, ks, vs, *rest,
                        sink_ref=sink, **kw)


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
    sink=None,
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
        sink=sink,
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
    work=None,  # ragged_walk's `Walk`, replicated (see below)
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
    if work is None:  # the same on every shard: built once, outside, for
        # the heads one shard is left with
        Hk, G = q.shape[1] // mesh.shape[axis_name], q.shape[2]
        work = ragged_walk((Hk, G), k_pool, v_pool, seg_page_table,
                           seg_kv_lens, meta, window, q.shape[0], q_block)

    def part(q, k_pool, v_pool, seg_pt, seg_kvl, meta, work, layer, *window):
        return ragged_paged_attention(
            q, k_pool, v_pool, seg_pt, seg_kvl, meta,
            window[0] if window else None, layer, work,
            q_block=q_block, scale=scale, softcap=softcap,
            interpret=interpret,
        )

    fn = jax.shard_map(
        part, mesh=mesh,
        in_specs=(heads, pool, pool, P(None, None), P(None), P(None, None),
                  P()) + (P(),) * len(scalars),
        out_specs=heads, check_vma=False,
    )
    return fn(q, k_pool, v_pool, seg_page_table, seg_kv_lens, meta, work,
              *scalars)


@functools.partial(
    jax.jit,
    static_argnames=("q_block", "interpret", "scale", "softcap", "name")
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
    work=None,  # ragged_walk((Hk, G), k_pool, v_pool, seg_page_table,
    #   seg_kv_lens, meta, window, T, q_block), for a caller that
    #   runs many layers on one plan and builds it once; None = built here.
    #   Its routine and pages a step are the call's
    *,
    q_block: int = DEFAULT_Q_BLOCK,
    scale=None,
    softcap: float = 0.0,
    interpret: bool = False,
    sink=None,  # f32 [Hk, G] sink logit a query head (decode_paged_attention)
    name=None,  # static: the custom call's name in a device trace
) -> jax.Array:
    """Returns [T, Hk, G, Dv] (Dv the value pool's width, the keys' D unless
    the pools differ); rows covered by no real segment return 0.
    Every segment's K/V (including its own chunk) must already be written
    to the pool. The compile key is (T, NW, SEG, q_block) — all functions
    of the T bucket, so variants stay at |T buckets|."""
    T, Hk, G, D = q.shape
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    kq, vq, ks, vs = split_scales(k_pool, v_pool, layer)
    quantized = ks is not None
    _, NP, PS, _, _ = kq.shape
    Dv = vq.shape[-1]
    MP = seg_page_table.shape[1]
    if T % q_block:
        raise ValueError(f"T {T} not a multiple of q_block {q_block}")
    if scale is None:
        scale = D**-0.5
    windowed = window is not None
    sinked = sink is not None
    if windowed:
        window = jnp.asarray(window, jnp.int32).reshape(())
    if quantized and sinked:
        raise NotImplementedError("a sink over an int8 KV pool")
    # the step's routine and its pages are the walk's, decided where its
    # lists were written (`ragged_walk`)
    if work is None:  # dynlint: disable=DYN-J001 (the argument's absence)
        work = ragged_walk((Hk, G), k_pool, v_pool, seg_page_table,
                           seg_kv_lens, meta, window, T, q_block)
    routine, tiles = work.routine, work.tiles
    steps = MP // tiles

    # entry g of the list is `unit * steps + step` of a live pair; the
    # unit names its q block and its segment (`meta`)
    def unit_of(g, wk):
        return _div(wk[g], steps)

    NB, QG = T // q_block, q_block * G
    token_minor = routine == "by_tiles" and Hk > 1
    if routine == "by_tiles":
        # a q block as ONE matrix. Rows (head, group, token): a head's rows
        # together for the columns' head mask, the token innermost so a
        # row's token is `row & (q_block - 1)`, laid out HERE, in XLA: one
        # transpose of q a call, as before. One KV head: q as it lies,
        # rows (token, group)
        qt = q.reshape(NB, q_block, Hk * G, D)
        if token_minor:
            qt = qt.transpose(0, 2, 1, 3)
        qt = qt.reshape(NB, Hk * QG, D)
        qo_block, state = (None, Hk * QG), (Hk * QG, 1)

        def qo_index(g, wk, mt, *rest):
            return (mt[1, unit_of(g, wk)], 0, 0)
    else:
        # group axis merged into the rows HERE, in XLA: a [.., G, D] block
        # pads G up to a full sublane tile in VMEM and Mosaic cannot
        # shape-cast every (QB, G) split (G == 1 fails to lower)
        qt = q.transpose(1, 0, 2, 3).reshape(Hk, T * G, D)
        qo_block, state = (Hk, QG), (Hk, QG, 1)

        def qo_index(g, wk, mt, *rest):
            return (0, mt[1, unit_of(g, wk)], 0)

    q_spec = pl.BlockSpec(qo_block + (D,), qo_index)
    kw = dict(page_size=PS * tiles, max_pages=steps, n_groups=G,
              q_block=q_block, scale=scale, softcap=softcap, routine=routine,
              token_minor=token_minor)
    if routine == "by_tiles":
        # `tiles` pages a step, each a block of its own on the same operand
        # that finds its page in the walk's filled-in table: the
        # token-major page as the pool holds it, one contiguous slab (a
        # single DMA), or at one KV head the pool as the step program
        # carries it, [L, NP, PS, D], a page one contiguous [PS, D] tile
        # (ops/paged_attention.py "One KV head")
        if Hk == 1:
            kq, vq = (p.reshape(p.shape[:3] + p.shape[4:]) for p in (kq, vq))
        zeros = (0,) * (kq.ndim - 2)

        def tile_index(t, g, wk, mt, pg, kl, ly, *rest):
            return (ly[0], pg[mt[0, unit_of(g, wk)] * MP
                              + _rem(wk[g], steps) * tiles + t]) + zeros

        def tile_specs(pool):
            return [pl.BlockSpec((None, None) + pool.shape[2:],
                                 functools.partial(tile_index, t))
                    for t in range(tiles)]

        in_specs = [q_spec] + tile_specs(kq) + tile_specs(vq)
        operands = (qt,) + (kq,) * tiles + (vq,) * tiles
    else:
        # the index maps read the list, then the unit's segment, then its
        # page-table row: no page is clamped, because no dead page is in
        # the list
        def kv_index(g, wk, mt, pt, kl, ly, *rest):
            return (ly[0], pt[mt[0, unit_of(g, wk)], _rem(wk[g], MP)], 0, 0, 0)

        def scale_index(g, wk, mt, pt, kl, ly, *rest):
            return kv_index(g, wk, mt, pt, kl, ly, *rest)[1:4]

        kv_spec = pl.BlockSpec((None, None, PS, Hk, D), kv_index)
        v_spec = pl.BlockSpec((None, None, PS, Hk, Dv), kv_index)
        if quantized:
            s_spec = pl.BlockSpec((None, PS, Hk), scale_index)
            in_specs = [q_spec, kv_spec, s_spec, v_spec, s_spec]
            operands = (qt, kq, ks, vq, vs)
        else:
            in_specs = [q_spec, kv_spec, v_spec]
            operands = (qt, kq, vq)
    if sinked:
        # each row's head's sink, the same in every block, in the rows' order
        sink = sink.astype(jnp.float32)
        rows = (jnp.repeat(sink.reshape(-1), q_block) if token_minor
                else jnp.tile(sink, (1, q_block)))
        in_specs.append(pl.BlockSpec(state, lambda g, *rest: (0,) * len(state)))
        operands += (rows.reshape(state),)
    kernel = functools.partial(
        _ragged_kernel, tiles=tiles, windowed=windowed, sinked=sinked,
        quantized=quantized, **kw)

    prefetch = (work.work, meta,
                seg_page_table if work.pages is None else work.pages,
                seg_kv_lens) + scalar_operands(layer, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # work, meta, seg_pt (the tile
        #   routine: the walk's filled-in one), seg_kvl, layer (+ window)
        grid=(work.n_work,),  # a traced bound: the live pairs, not NW * MP
        in_specs=in_specs,
        out_specs=pl.BlockSpec(qo_block + (Dv,), qo_index),
        scratch_shapes=[
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(state[:-1] + (Dv,), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape[:-1] + (Dv,), q.dtype),
        interpret=interpret,
        **({"name": name} if name else {}),
    )(*prefetch, *operands)
    # back to [T, Hk, G, Dv]; a row that no visited unit covers (bucket
    # padding, a segment without tokens) was never written: define it, as 0
    if token_minor:
        out = out.reshape(NB, Hk * G, q_block, Dv).transpose(0, 2, 1, 3)
    elif routine == "by_heads":
        out = out.reshape(Hk, T, G, Dv).transpose(1, 0, 2, 3)
    out = out.reshape(T, Hk, G, Dv)
    return jnp.where(work.covered[:, None, None, None], out, 0)
