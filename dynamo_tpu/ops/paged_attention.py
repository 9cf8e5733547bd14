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

Grid: a WORK LIST of live pages, one grid step each, its length a traced
bound. A call costs what its rows hold, not what the page table could:
`decode_work_list` builds, in XLA from the lengths and the sliding window
alone, the (row, page) of every live page — row b's run from the first
page its window shows to the page of its newest token, a pad row
(kv_len 0) has none — rows in order and pages ascending, so a row's
running softmax state lives across its pages: it is initialised on the
row's first live page and written out on its last. The index maps read
the list, then the page table; a step that is never taken costs nothing
(under the old grid (B, MP) a 450-token row in a 4096-token table took 64
steps for 8 pages). A row with no live page is never visited and its
output is defined by the wrapper (0). No shape of the call depends on the
lengths: one program whatever the rows hold. A caller that runs many
layers on one set of lengths builds the list once and hands it in (`work`;
models/llama.py does, above its layer scan).

All kv heads are processed per step. A token-major page [PS, Hk, D] is one
CONTIGUOUS slab in the pool, so each grid step issues a single large DMA a
page (the head-major layout needed Hk strided chunks per page). What a
step does with its pages is decided in one place from the static shapes
the kernel sees (`page_routine`; `decode_walk` asks there and hands the
answer to the wrapper with its lists, a `Walk`; the runner's report asks
there too), one walk whatever the answer:

- an int8 pool: `_page_by_heads`, a batched float32 product a kv head, the
  scales folded in per (token, head). Nothing else takes it.
- G = 1 where Hk fills whole sublane tiles of the pool's dtype, no sink,
  values as wide as keys (phi-3's MHA): `_page_by_rows`, every query row
  against the page read as one [PS * Hk, D] matrix.
- every other dense pool (GQA at any Hk and G, one KV head, G = 1 at half
  a tile, a sink, values narrower than keys): `_pages_by_tiles`, the same
  form for G query heads a KV head and several pages a step: all Hk * G
  query rows against the step's pages read as one [N * Hk, D] matrix in
  the pool's dtype, the columns of other KV heads masked, the values
  through `_pv_exact` at their own width; scores, softmax state and
  accumulator float32. Latent attention's decode kernel
  (ops/mla_attention.py `decode_mla_attention`) takes it too, on this
  walk and this body with a call of its own: one KV head whose values are
  the leading columns of the key tiles, ONE block a page (`v_pool` None
  to `decode_walk` and `page_bytes`).

Why no head is ever brought together. The pools lie in HBM as the step
programs carry them, `T(4,128)(2,1)` / `T(8,128)(2,1)` over the minor pair
(Hk, D): heads on sublanes (two bf16 heads share a 32-bit one), a head's
vector on lanes. A view [L, NP, PS, Hk * D], a head a lane-aligned slice
of a row, is another byte order, and XLA lays the whole stack out again
for it (a `reshape` that is no bitcast: tests/test_mosaic_compile.py shows
one). A head read out of the 5-d block in VMEM (`ref[:, h, :]`, or a
slice of the loaded block, or a batched product) is a strided, half-word
gather: 2.6 us a page at mimo-v2-flash's global geometry where the
page's DMA is 0.24 (float32 `by_heads`: 5.7). Read as the matrix
[PS * Hk, D] the block moves nowhere: the MXU multiplies every query row
by every (token, head) row, Hk times the useful products, which it has
to spare, and the mask keeps a head's own: 0.5 us a page (my chip runs,
PR 41). One KV head is the case with nothing to mask, and the one whose
view is free: a pool [L, NP, PS, 1, D] holds the bytes of a row-major
[L, NP, PS, D] array, the 5-d operand would be padded token by token
((1, D) tiles) and converted whole in front of every call, and the 4-d
view is the layout itself, a page one contiguous [PS, D] tile.

Pages a grid step. A step costs ~0.35 us whatever it brings, and a page is
2 * PS * D bytes of K or V a head (0.04 us of DMA at one head of 128, 0.24
and 0.48 us for mimo-v2-flash's two kinds of page), so a step of the tile
routine brings `step_tiles` pages of the row, from the page's bytes: each a
block of its own on the same operand, and the walk is the same walk at
that granularity: `decode_walk` lists (row, step) pairs and, beside them,
the page table with its dead entries filled in, so an index map is two
SMEM reads and no clamp. A tile past a row's live pages repeats a live
page and is fetched all the same, which is why large pages ride few.

The reference framework ships CUDA kernels for its block engine
(lib/llm/src/kernels/block_copy.cu, lib/kvbm-kernels/cuda/
tensor_kernels.cu); attention itself lives in vLLM. This is the TPU-native
equivalent of that hot path.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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


def decode_work_list(kv_lens, window, page_size: int, max_pages: int):
    """The decode kernel's grid as a list of live pages, built in XLA from
    the lengths (and the window) alone: (work[W] int32, n_work int32).

    A decode row is the walk's one-query case (`live_pages`): its live
    pages run from the first its window shows (0 without one) to
    `(kv_len - 1) // PS`; a pad row (kv_len 0) has none. W = B * MP is
    the static bound (`work_list`)."""
    first, last = live_pages(kv_lens - 1, kv_lens - 1, kv_lens, window,
                             page_size, max_pages)
    return work_list(first, jnp.where(kv_lens > 0, last - first + 1, 0),
                     max_pages)


def page_routine(Hk: int, G: int, dtype, quantized: bool, sinked: bool,
                 one_width: bool) -> str:
    """What a decode step does with its pages, THE decision, from the static
    shapes one call of the kernel sees (a tensor-parallel shard: its local
    heads), the pool's dtype, whether a sink rides along and whether the
    values are as wide as the keys, in this order: "by_heads" for an int8
    pool, whose scales ride per (token, head), and for nothing else;
    "by_rows" at G = 1 when Hk fills whole sublane tiles of the pool's
    dtype, so the page reads as a matrix for free, with no sink and one
    width; "by_tiles" for every other dense call (one KV head is its
    one-head case). `decode_walk` asks here, and the call reads the answer
    off the walk; `ModelRunner.device_report` asks here too."""
    if quantized:
        return "by_heads"
    if (G == 1 and not sinked and one_width
            and Hk % (32 // jnp.dtype(dtype).itemsize) == 0):
        return "by_rows"
    return "by_tiles"


# bytes a grid step of the tile routine brings at most (K + V of its pages
# as the pool holds them). A step costs ~0.35 us whatever it brings (PR 39),
# so small pages ride several a step; a tile past a row's live pages repeats
# a live page and is fetched all the same, so large ones ride few.
# scripts/bench_attn.py's sweep on a v5e (my chip runs, PR 41) set it:
# see `step_tiles`
STEP_BYTES = 1 << 20


def page_bytes(Hk: int, k_pool, v_pool) -> int:
    """K + V of one page of one layer as ONE call of the kernel holds them:
    `Hk` KV heads (a tensor-parallel shard's local heads, NOT the pool's
    head axis: a walk is built outside `shard_map`, where the pool still
    has every head), a head's vector padded to whole lane rows. `v_pool`
    None: the values are columns of the key page (latent attention), ONE
    block, reckoned once."""
    def lanes(a):
        return 0 if a is None else -(-a.shape[-1] // 128) * 128
    PS = k_pool.shape[-3]
    return PS * Hk * (lanes(k_pool) + lanes(v_pool)) * k_pool.dtype.itemsize


def step_tiles(nbytes: int, max_pages: int) -> int:
    """Pages a step of the tile routine brings, from a page's `nbytes`
    (`page_bytes`) under a page table `max_pages` wide: the largest power
    of two, at most 8, that keeps a step within STEP_BYTES and divides the
    table, so a row's steps tile it."""
    tiles = 8
    while tiles > 1 and tiles * nbytes > STEP_BYTES:
        tiles //= 2
    return math.gcd(max_pages, tiles)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("work", "n_work", "covered", "pages"),
    meta_fields=("routine", "tiles"))
@dataclasses.dataclass(frozen=True)
class Walk:
    """The lists one call of a walking kernel takes, WITH the decision they
    were built for: the walk's maker (`decode_walk`; the ragged kernel's
    `ragged_walk`) decides the routine and the pages a step from the heads
    one call sees, and the kernel's wrapper reads both from here and never
    decides again, so a list cannot be decoded at another granularity than
    it was written at (under tensor parallelism the maker runs outside
    `shard_map`, on pools that still have every head). A pytree: the lists
    are its leaves, `routine` and `tiles` static beside them, so it rides
    `jit`, `shard_map` and a layer scan's closure like the tuple it was."""
    work: jax.Array  # [W] int32: unit * steps_a_unit + step of a live pair
    n_work: jax.Array  # int32: the live pairs, the grid's traced bound
    covered: Optional[jax.Array]  # ragged: [T] bool, rows some unit writes
    pages: Optional[jax.Array]  # by_tiles: `filled_page_table`, else None
    routine: str  # page_routine / ragged_page_routine
    tiles: int  # pages a grid step (1 unless by_tiles)


def decode_step(heads, k_pool, v_pool, max_pages: int, sinked):
    """(routine, pages a grid step) of a decode call under a page table
    `max_pages` wide: `page_routine` at `heads` = (Hk, G) of ONE call, the
    pools' dtype and widths and `sinked`, and for "by_tiles" `step_tiles`
    of `page_bytes` at THOSE heads (every other routine: 1). `v_pool` None:
    latent attention's one pool, the values the leading columns of the key
    page (ops/mla_attention.py), so never one width. Shapes and dtypes
    alone are read: `decode_walk` asks with the step's arrays,
    `ModelRunner.device_report` with the pools it holds."""
    quantized = isinstance(k_pool, dict)
    kq, vq = (p["q"] if isinstance(p, dict) else p for p in (k_pool, v_pool))
    routine = page_routine(*heads, kq.dtype, quantized, sinked,
                           vq is not None and kq.shape[-1] == vq.shape[-1])
    if routine != "by_tiles":
        return routine, 1
    return routine, step_tiles(page_bytes(heads[0], kq, vq), max_pages)


def decode_walk(heads, k_pool, v_pool, page_table, kv_lens, window,
                sinked) -> Walk:
    """The `Walk` of a decode call, for a caller that runs many layers on
    one set of lengths and builds it once. `heads` = (Hk, G) of ONE call (a
    shard's local heads); with the pools' dtype and widths and `sinked`
    they decide the routine and the pages a step (`decode_step`), and the
    call takes both from the walk. `v_pool` None: latent attention's one
    pool. `decode_work_list`'s pair for "by_rows" and "by_heads";
    for "by_tiles" the same walk over steps of `tiles` pages, entry w =
    `row * steps_a_row + step`, so that `entry * tiles + t` is the place of
    the step's t-th tile in `pages`, the page table flattened with every
    dead entry of a row replaced by its nearest live one. A tile past either
    end of the row's live pages thus repeats a live page (its slots are
    masked by position), and the kernel reads no dead page-table entry and
    no dead page. Built from compares and masked sums alone: a gather of
    B * MP scalars cost more than the kernel's call (175 us against 104, my
    chip run, PR 39)."""
    kq = k_pool["q"] if isinstance(k_pool, dict) else k_pool
    PS, MP = kq.shape[-3], page_table.shape[1]
    routine, tiles = decode_step(heads, k_pool, v_pool, MP, sinked)
    if routine != "by_tiles":
        work, n_work = decode_work_list(kv_lens, window, PS, MP)
        return Walk(work, n_work, None, None, routine, 1)
    work, n_work = decode_work_list(kv_lens, window, PS * tiles, MP // tiles)
    first, last = live_pages(
        kv_lens - 1, kv_lens - 1, kv_lens, window, PS, MP)
    return Walk(work, n_work, None,
                filled_page_table(page_table, first, last), routine, tiles)


def filled_page_table(page_table, first, last):
    """A page table [R, MP], flattened, with every entry of row r outside
    its live run first[r] .. last[r] replaced by the run's nearest end: what
    a walk of several pages a step indexes (`decode_walk`; the ragged
    kernel's `ragged_walk`, a row a segment). Compares and masked sums,
    no gather (`decode_walk`). A row without a live run (first > last)
    names page 0 throughout; no walk visits it."""
    first, last = first[:, None], last[:, None]
    col = lax.iota(jnp.int32, page_table.shape[1])[None, :]

    def entry_at(i):  # page_table[r, i[r]] as [R, 1]
        return jnp.sum(jnp.where(col == i, page_table, 0), axis=1,
                       keepdims=True)

    pages = jnp.where(col < first, entry_at(first),
                      jnp.where(col > last, entry_at(last), page_table))
    return pages.reshape(-1)


def work_list(first, count, max_pages: int):
    """A walk as one packed list, (work[W] int32, n_work int32): unit u (a
    decode row; a ragged work unit) brings `count[u]` pages from
    `first[u]` up. Entry w < n_work is `unit * MP + page` of the w-th
    live (unit, page) pair, units in order and each unit's pages
    ascending; W = U * MP is the static bound and the entries past n_work
    are never visited (they stay inside the table all the same: an index
    map may read one step ahead). One packed list: scalar-prefetch
    operands live in SMEM, where the worker's default shape (bucket 64,
    MP 256) already keeps a 64 KB page table."""
    U = first.shape[0]
    ends = jnp.cumsum(count)
    # unit of entry w = how many units end at or before it (a unit with no
    # live page ends where it starts and is stepped over); its page =
    # first[unit] + (w - start[unit]). Both as [W, U] compares-and-sums:
    # one small fusion, no gather and no sort
    w = lax.iota(jnp.int32, U * max_pages)
    before = w[:, None] >= ends[None, :]
    unit = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), U - 1)
    here = lax.iota(jnp.int32, U)[None, :] == unit[:, None]
    shift = first - (ends - count)
    page = w + jnp.sum(jnp.where(here, shift[None, :], 0), axis=1)
    page = jnp.clip(page, 0, max_pages - 1)
    return unit * max_pages + page, ends[-1]


def live_pages(q_first, q_last, kv_len, window, page_size: int,
               max_pages: int):
    """(first, last) live page of a run of queries at positions q_first ..
    q_last over a sequence that holds kv_len tokens: causality and the
    length end the run at the page of min(q_last, kv_len - 1), and under
    a sliding window nothing below `q_first - window + 1` is visible to
    any of them, so the pages wholly below it are dead. THE walk's rule:
    a decode row is the run of one query at kv_len - 1, a ragged work
    unit the rows of one q block that belong to one segment. Shared by
    the work lists (XLA, arrays) and the kernels (SMEM scalars) so the
    two cannot drift apart. Both stay inside the page table whatever the
    lengths say: a length past MP * PS walks the table's MP pages, and
    never makes a list longer than its W entries."""
    end = jnp.maximum(jnp.minimum(q_last, kv_len - 1), 0)
    last = jnp.minimum(_div(end, page_size), max_pages - 1)
    return jnp.minimum(_div(_window_lo(q_first, window), page_size), last), last


def _div(a, b: int):
    """a // b for a >= 0 and a static b > 0. `//` and `%` on traced ints
    are floor_divide / remainder, a dozen equations each for signs that
    cannot occur here; the walk's index maps and body hold ten of them,
    traced and lowered again for every step program (42 for the cell)."""
    return lax.div(a, np.int32(b))


def _rem(a, b: int):
    return lax.rem(a, np.int32(b))


def _window_lo(q_pos, window):
    """The lowest position a query at q_pos sees (0 without a window)."""
    if window is None:
        return jnp.zeros_like(q_pos)
    return jnp.where(window > 0, jnp.maximum(q_pos - window + 1, 0), 0)


def _decode_kernel_body(
    work_ref,  # [B * MP] int32 (SMEM): row * MP + page of each live page
    page_table_ref,  # [B, MP] int32 (SMEM)
    kv_lens_ref,  # [B] int32 (SMEM)
    win_ref,  # [1] int32 sliding window (0 = global) or None (no-window
    #   compile: Gemma-2 alternates sliding/global per layer with a
    #   TRACED scalar, so the window rides as a prefetch operand)
    q_ref,  # [Hk, G, D] all query heads of the row ([Hk, D] by rows,
    #   [Hk * G, D] by tiles)
    k_ref,  # [PS, Hk, D] one token-major page of keys (one contiguous DMA);
    #   by tiles: the step's pages, a tuple of such blocks ([PS, D] tiles
    #   of the 4-d view at one head)
    v_ref,  # like k_ref, at the values' width Dv
    ks_ref,  # [PS, Hk] f32 per-vector K scales (int8 KV) or None
    vs_ref,  # [PS, Hk] f32 per-vector V scales or None
    o_ref,  # like q_ref, Dv wide
    # scratch (persist across a row's pages)
    m_ref,  # f32 running max: [Hk, G, 1] ([Hk, 1] by rows, [Hk * G, 1] by
    #   tiles)
    l_ref,  # f32 running denom, like m_ref
    acc_ref,  # f32 running numerator, like o_ref
    *,
    page_size: int,  # tokens a grid step covers: a page (by tiles: its
    #   tiles' pages together, and max_pages the steps a row can take)
    max_pages: int,
    scale: float,
    softcap: float = 0.0,  # Gemma-2 attention-score soft capping (0 = off)
    routine,  # the per-page routine, the function (_PAGE_ROUTINES)
    sink_ref=None,  # f32 like m_ref: a learned sink logit a query head, one
    #   more column of the softmax that gives no value (None: no column)
):
    entry = work_ref[pl.program_id(0)]
    b = _div(entry, max_pages)
    i = _rem(entry, max_pages)
    kv_len = kv_lens_ref[b]
    window = None if win_ref is None else win_ref[0]
    first, last = live_pages(kv_len - 1, kv_len - 1, kv_len, window,
                             page_size, max_pages)

    @pl.when(i == first)
    def _init():
        if sink_ref is None:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        else:  # the sink seeds the running softmax: a score with no value
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # every page the walk visits is live; its first and last may be
    # partly so: slots past kv_len, and slots below the window's lo
    n_valid = jnp.minimum(kv_len - i * page_size, page_size)
    lo_in_page = jnp.clip(
        _window_lo(kv_len - 1, window) - i * page_size, 0, page_size)
    routine(
        q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref, acc_ref,
        n_valid, lo_in_page, scale=scale, softcap=softcap)

    @pl.when(i == last)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _page_by_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                   acc_ref, n_valid, lo_in_page, *, scale, softcap):
    """One page into the running softmax, a batched MXU product a kv head:
    [G, D] x [D, PS] and [G, PS] x [PS, D]. Right where G query heads share
    each page of K (GQA) and for any Hk."""
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


def _page_by_rows(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                  acc_ref, n_valid, lo_in_page, *, scale, softcap):
    """One page into the running softmax at G = 1 (MHA), where a batched
    product would load the MXU a head for ONE row of output and move the
    page head-major first. Here the page stays as it lies: [PS, Hk, D]
    read as the matrix [PS * Hk, D] (free: Hk fills whole sublane tiles),
    all Hk query rows against all of it in ONE product, s[r, (p, h)] =
    q[r] · k[p, h], of which the entries with h == r are the scores and
    the rest are masked like dead slots. Softmax then runs on [Hk, PS * Hk]
    with every lane in use and state [Hk, 1]; the masked probabilities ARE
    the block-diagonal left operand of the PV product [Hk, PS * Hk] x
    [PS * Hk, D]. K and V go to the MXU in the pool's dtype, never cast:
    bf16 x bf16 products are exact in the f32 accumulator, and the f32
    probabilities go as three bf16 terms (8 + 8 + 8 mantissa bits, exact),
    stacked on rows so V is loaded once. The MXU does Hk times the useful
    products; it has them to spare, the VPU and the relayouts did not."""
    del ks_ref, vs_ref  # dense pools only (decode_paged_attention)
    PS, Hk, D = k_ref.shape
    N = PS * Hk
    q = q_ref[...]  # [Hk, D]
    k = k_ref[...].reshape(N, D)
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [Hk, N]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    col = lax.broadcasted_iota(jnp.int32, s.shape, 1)  # p * Hk + h
    row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    valid = ((_rem(col, Hk) == row) & (col < n_valid * Hk)
             & (col >= lo_in_page * Hk))
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]  # [Hk, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # [Hk, N]
    alpha = jnp.exp(m_prev - m_new)
    l_add = jnp.sum(p, axis=1, keepdims=True)

    pv = _pv_exact(p, v_ref[...].reshape(N, D))
    acc_ref[...] = acc_ref[...] * alpha + pv
    l_ref[...] = l_ref[...] * alpha + l_add
    m_ref[...] = m_new


def _pv_exact(p, v):
    """[R, N] f32 probabilities x [N, D] values in the pool's dtype, V
    never cast: below f32 the probabilities go as three terms of V's dtype
    (bf16: 8 + 8 + 8 mantissa bits, exact), stacked on rows so the MXU
    loads V once."""
    if v.dtype == jnp.float32:
        return jnp.dot(p, v, preferred_element_type=jnp.float32)
    R = p.shape[0]
    terms = []
    for _ in range(3):
        terms.append(p.astype(v.dtype))
        p = p - terms[-1].astype(jnp.float32)
    pv = jnp.dot(jnp.concatenate(terms, axis=0), v,
                 preferred_element_type=jnp.float32)  # [3 * R, D]
    return pv[:R] + pv[R:2 * R] + pv[2 * R:]


def _pages_by_tiles(q_ref, k_refs, v_refs, ks_ref, vs_ref, m_ref, l_ref,
                    acc_ref, n_valid, lo_in_page, *, scale, softcap):
    """A step's pages into the running softmax, `_page_by_rows` carried to G
    query heads a KV head and to several pages a step: every query row,
    [Hk * G, D], against the step's tiles as they lie in the pool, each
    token-major [PS, Hk, D] block read as the matrix [PS * Hk, D] and the
    tiles stacked on rows, in ONE product: s[r, (t, p, h)] = q[r] .
    k[t, p, h], of which the entries with h == r's KV head are the scores
    and the rest are masked like dead slots. The masked probabilities ARE
    the block-diagonal left operand of the PV product, at the values' own
    width. Nothing is moved to bring a head's tokens together: the pools
    hold heads on sublanes, and a head read out of the block as
    `ref[:, h, :]`, a product a head, cost 2.6 us a page where this costs
    0.5 (PERF.md section 6, PR 41). K, V and q go to the MXU in the pool's
    dtype (`_pv_exact`); scores, softmax state and accumulator are f32. The
    MXU does Hk times the useful products, and has them to spare. At one
    KV head a tile is the [PS, D] block of the 4-d view and no column is
    another head's. A tile that repeats a live page (`decode_walk`) sits
    past n_valid or below lo_in_page like any dead slot. `v_refs is k_refs`
    (latent attention, ops/mla_attention.py): the values are the leading
    columns of the same tiles, as wide as the accumulator."""
    del ks_ref, vs_ref  # dense pools only (page_routine)

    def rows(refs):  # [N * Hk, width], rows (tile, token, head)
        return jnp.concatenate(
            [r[...].reshape(-1, r.shape[-1]) for r in refs], axis=0)

    Hk = 1 if len(k_refs[0].shape) == 2 else k_refs[0].shape[1]
    R = q_ref.shape[0]
    k = rows(k_refs)
    s = lax.dot_general(
        q_ref[...], k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32
    ) * scale  # [R, N * Hk]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    col = lax.broadcasted_iota(jnp.int32, s.shape, 1)  # (t * PS + p) * Hk + h
    if Hk > 1:  # (one head: the kernel body PR 39 measured, to the equation)
        n_valid, lo_in_page = n_valid * Hk, lo_in_page * Hk
    valid = (col < n_valid) & (col >= lo_in_page)
    if Hk > 1:
        head = _div(lax.broadcasted_iota(jnp.int32, (R, 1), 0), R // Hk)
        valid &= (col & (Hk - 1) if Hk & (Hk - 1) == 0
                  else _rem(col, Hk)) == head
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]  # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_add = jnp.sum(p, axis=1, keepdims=True)
    v = k[:, :acc_ref.shape[-1]] if v_refs is k_refs else rows(v_refs)
    pv = _pv_exact(p, v)
    acc_ref[...] = acc_ref[...] * alpha + pv
    l_ref[...] = l_ref[...] * alpha + l_add
    m_ref[...] = m_new


_PAGE_ROUTINES = {"by_heads": _page_by_heads, "by_rows": _page_by_rows,
                  "by_tiles": _pages_by_tiles}


def _decode_kernel(wk, pt, kl, ly, q, k, v, o, m, l, acc, **kw):
    _decode_kernel_body(wk, pt, kl, None, q, k, v, None, None, o, m, l, acc,
                        **kw)


def _decode_kernel_win(wk, pt, kl, ly, win, q, k, v, o, m, l, acc, **kw):
    _decode_kernel_body(wk, pt, kl, win, q, k, v, None, None, o, m, l, acc,
                        **kw)


def _decode_kernel_tiles(wk, pg, kl, ly, *refs, tiles, windowed, sinked,
                         **kw):
    """The tile routine's call: `pg` the walk's filled-in page table where
    the others take the page table; K and V `tiles` refs each, a sink
    operand behind them where there is one."""
    win, refs = (refs[0], refs[1:]) if windowed else (None, refs)
    q, k, v, rest = (refs[0], refs[1:1 + tiles], refs[1 + tiles:1 + 2 * tiles],
                     refs[1 + 2 * tiles:])
    sink, rest = (rest[0], rest[1:]) if sinked else (None, rest)
    _decode_kernel_body(wk, pg, kl, win, q, k, v, None, None, *rest,
                        sink_ref=sink, **kw)


def _decode_kernel_int8(wk, pt, kl, ly, q, k, ks, v, vs, o, m, l, acc, **kw):
    _decode_kernel_body(wk, pt, kl, None, q, k, v, ks, vs, o, m, l, acc,
                        **kw)


def _decode_kernel_int8_win(wk, pt, kl, ly, win, q, k, ks, v, vs, o, m, l,
                            acc, **kw):
    _decode_kernel_body(wk, pt, kl, win, q, k, v, ks, vs, o, m, l, acc,
                        **kw)


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
    work=None,  # decode_walk's `Walk`, replicated (see below)
    *,
    scale=None,
    softcap: float = 0.0,
    interpret: bool = False,
    sink=None,  # f32 [Hk, G], sharded with the heads
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
    sinks = () if sink is None else (sink,)
    if work is None:  # the same on every shard: built once, outside, for
        # the heads one shard is left with
        Hk, G = q.shape[1] // mesh.shape[axis_name], q.shape[2]
        work = decode_walk((Hk, G), k_pool, v_pool, page_table, kv_lens,
                           window, sink is not None)

    def part(q, k_pool, v_pool, page_table, kv_lens, work, *rest):
        layer, *window = rest[:len(scalars)]
        return decode_paged_attention(
            q, k_pool, v_pool, page_table, kv_lens,
            window[0] if window else None, layer, work,
            scale=scale, softcap=softcap, interpret=interpret,
            sink=rest[-1] if sinks else None,
        )

    fn = jax.shard_map(
        part,
        mesh=mesh,
        in_specs=(heads, pool, pool, P(None, None), P(None), P())
        + (P(),) * len(scalars) + (P(axis_name, None),) * len(sinks),
        out_specs=heads,
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, page_table, kv_lens, work, *scalars, *sinks)


@functools.partial(
    jax.jit, static_argnames=("interpret", "scale", "softcap", "name")
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
    work=None,  # decode_walk((Hk, G), k_pool, v_pool, page_table, kv_lens,
    #   window, sinked), for a caller that runs many layers on one set of
    #   lengths and builds it once; None = built here. Its routine and
    #   pages a step are the call's
    *,
    scale=None,  # static score-scale override (query_pre_attn_scalar)
    softcap: float = 0.0,  # Gemma-2 logit soft capping (static; 0 = off)
    interpret: bool = False,
    sink=None,  # f32 [Hk, G]: a learned sink logit a query head (None: no
    #   sink; toolkit.paged_attention_jnp says what it is)
    name=None,  # static: the custom call's name in a device trace (None:
    #   the kernel function's)
) -> jax.Array:
    """Returns [B, Hk, G, Dv], Dv the value pool's width (the keys' D
    unless the pools differ). KV for the current token must already be
    written to the pool (same contract as paged_attention_jnp)."""
    B, Hk, G, D = q.shape
    k_pool, v_pool, layer = stacked_pools(k_pool, v_pool, layer)
    kq, vq, ks, vs = split_scales(k_pool, v_pool, layer)
    quantized = ks is not None
    _, NP, PS, _, _ = kq.shape
    Dv = vq.shape[-1]
    MP = page_table.shape[1]
    if scale is None:
        scale = D**-0.5
    windowed = window is not None
    sinked = sink is not None
    if windowed:
        window = jnp.asarray(window, jnp.int32).reshape(())
    if quantized and sinked:
        raise NotImplementedError("a sink over an int8 KV pool")
    # the per-page routine and the pages a grid step brings are the walk's,
    # decided where its lists were written (`decode_walk`)
    if work is None:  # dynlint: disable=DYN-J001 (the argument's absence)
        work = decode_walk((Hk, G), k_pool, v_pool, page_table, kv_lens,
                           window, sinked)
    routine, tiles = work.routine, work.tiles
    steps = MP // tiles  # the steps a row can take

    def row_of(w, wk):
        return _div(wk[w], steps)

    def kv_index(w, wk, pt, kl, ly, *rest):
        return (ly[0], pt[row_of(w, wk), _rem(wk[w], MP)], 0, 0, 0)

    def scale_index(w, wk, pt, kl, ly, *rest):
        return kv_index(w, wk, pt, kl, ly, *rest)[1:4]

    if routine in ("by_rows", "by_tiles"):  # the query heads as one matrix
        q = q.reshape(B, Hk * G, D)
        qo_block, state = (None, Hk * G, D), (Hk * G, 1)

        def qo_index(w, wk, *rest):
            return (row_of(w, wk), 0, 0)
    else:
        qo_block, state = (None, Hk, G, D), (Hk, G, 1)

        def qo_index(w, wk, *rest):
            return (row_of(w, wk), 0, 0, 0)

    q_spec = pl.BlockSpec(qo_block, qo_index)
    o_block = qo_block[:-1] + (Dv,)
    # one token-major page of one layer = one contiguous PS*Hk*D slab: a
    # single DMA, with a legal (PS, Hk, D) tile (minor dims (Hk, D))
    kv_spec = pl.BlockSpec((None, None, PS, Hk, D), kv_index)
    kw = dict(page_size=PS, max_pages=MP, scale=scale, softcap=softcap,
              routine=_PAGE_ROUTINES[routine])
    if quantized:
        kernel = functools.partial(
            _decode_kernel_int8_win if windowed else _decode_kernel_int8, **kw
        )
        # (None, PS, Hk): minor dims are full array dims — legal tile
        s_spec = pl.BlockSpec((None, PS, Hk), scale_index)
        v_spec = pl.BlockSpec((None, None, PS, Hk, Dv), kv_index)
        in_specs = [q_spec, kv_spec, s_spec, v_spec, s_spec]
        operands = (q, kq, ks, vq, vs)
    elif routine == "by_tiles":
        # `tiles` pages a step, each a block of its own on the same operand
        # that finds its page in the walk's filled-in table: the token-major
        # page as the pool holds it, or at one KV head the pool as the step
        # program carries it, [L, NP, PS, D], a page one contiguous [PS, D]
        # tile
        kw.update(page_size=PS * tiles, max_pages=steps)
        kernel = functools.partial(
            _decode_kernel_tiles, tiles=tiles, windowed=windowed,
            sinked=sinked, **kw)
        if Hk == 1:
            kq, vq = (p.reshape(p.shape[:3] + p.shape[4:]) for p in (kq, vq))
        zeros = (0,) * (kq.ndim - 2)

        def tile_index(t, w, wk, pg, kl, ly, *rest):
            return (ly[0], pg[wk[w] * tiles + t]) + zeros

        def tile_specs(pool):
            return [pl.BlockSpec((None, None) + pool.shape[2:],
                                 functools.partial(tile_index, t))
                    for t in range(tiles)]

        in_specs = [q_spec] + tile_specs(kq) + tile_specs(vq)
        operands = (q,) + (kq,) * tiles + (vq,) * tiles
        if sinked:
            in_specs.append(pl.BlockSpec(state, lambda w, *rest: (0, 0)))
            operands += (sink.astype(jnp.float32).reshape(state),)
    else:
        kernel = functools.partial(
            _decode_kernel_win if windowed else _decode_kernel, **kw
        )
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (q, kq, vq)

    prefetch = (work.work, page_table if work.pages is None else work.pages,
                kv_lens) + scalar_operands(layer, window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # work, page_table (the tile
        #   routine: the walk's filled-in one), kv_lens, layer (+ window)
        grid=(work.n_work,),  # a traced bound: the live pages, not B * MP
        in_specs=in_specs,
        out_specs=pl.BlockSpec(o_block, qo_index),
        scratch_shapes=[
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(state, jnp.float32),
            pltpu.VMEM(q.shape[1:-1] + (Dv,), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (Dv,), q.dtype),
        interpret=interpret,
        **({"name": name} if name else {}),
    )(*prefetch, *operands)
    # a row with no live page (a pad row) is never visited, so its output
    # block is never written: define it, as 0
    return jnp.where((kv_lens > 0)[:, None, None, None],
                     out.reshape(B, Hk, G, Dv), 0)
