"""Pallas TPU top-k SELECT for a decode step of a model with an indexer
(DeepSeek-V3.2: models/mla.py `_selected_attention`).

A decode row's index scores f32 [C] become the K pool cells its attention
gathers, with no sort: nothing downstream wants an order, only the SET of
the K best (ties towards the lower position, as `lax.top_k` breaks them),
the live ones first. One Mosaic call a layer, a grid over blocks of up to 8
rows that ride the sublanes, so that every step below is a walk over the
row's C / 128 lane tiles with the rows side by side; a block of 4, 2 or 1
rows puts 2, 4 or 8 consecutive tiles of each row into a register's 8
sublanes (tile F t + f of row r at sublane f R + r of register t), so that
a short batch walks as many fewer registers:

1. the scores' order-preserving int32 keys (the float's bits, the lower 31
   flipped under a set sign: -0.0 under +0.0, as `topk_mask` has them);
2. the k-th largest key by a radix select, 32 passes of compare and count
   over the keys resident in VMEM;
3. the tie rule: everything above the threshold and of its equals the
   lowest positions that fill K, by a prefix count in position order (a
   scan inside each lane tile, a carry from tile to tile);
4. the chosen set as `pack_chosen` lays it out (cell s is bit s // W of word
   s % W), the positions at or past `n_live` left out;
5. the compaction: each chosen position has to move left by the number of
   positions not chosen before it, and does so one bit of that distance a
   stage, lowest bit first (the reverse butterfly of a parallel compress:
   no two elements ever want one slot). What moves is the pool's cell of
   the position, `page_table[b, s // PS] * PS + s % PS`, which for the
   whole context is a broadcast of the page table's entries over their
   pages' slots (made in front of the kernel, one small fusion), so no
   lookup follows. Live positions are a prefix of the context, hence live
   first in position order.

The walks take a slab of up to 8 registers a loop iteration as ONE array
[8 n, 128] (one load, one store and one operation of each kind a slab, which
Mosaic unrolls by register: the chains of a slab's registers overlap, and
the kernel's text stays a few hundred operations, so that lowering it into
each of a worker's decode programs costs hundredths of a second), and the
scores come in by chunks of up to 16 tiles along a second grid axis, so
that their copy overlaps the keys' making. VMEM at the cell's shapes (8
rows x 36,864): two scratch arrays of 1.2 MB and the chunks' buffers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
_INT_MIN = -(1 << 31)


def _grab(ref, base, n):
    """Registers base .. base + n - 1 of a [*, 8, 128] scratch as [8 n, 128]."""
    return ref[pl.ds(base, n)].reshape(n * SUBLANES, LANES)


def _put(ref, base, x):
    n = x.shape[0] // SUBLANES
    ref[pl.ds(base, n)] = x.reshape(n, SUBLANES, LANES)


def _select_kernel(scores_ref,  # [R, L] f32: a chunk of the rows, dead positions at -inf
                   cells_ref,  # [R, L] int32: the pool's cell of each position
                   nlive_ref,  # [R, 1] int32: positions below it are live
                   out_ref,  # [R, Kp] int32: the chosen cells, packed
                   words_ref,  # [R, Wp] int32: the chosen set as bit words
                   key_ref,  # [V + W, 8, 128] int32: keys, then distances
                   val_ref,  # [V + W, 8, 128] int32: cell + 1, 0 = a hole
                   bits_ref,  # [Wp / 128, R, 128] int32: the words as they fill
                   *, k: int, words: int, slab: int):
    R, L = scores_ref.shape
    F = SUBLANES // R  # tiles of a row a register holds
    i32 = jnp.int32

    # -- 1. a chunk's keys, a register a leading index; the cells (+ 1) beside
    chunk = pl.program_id(1)
    b = lax.bitcast_convert_type(scores_ref[...], i32)
    keys = lax.select(b < 0, b ^ i32(0x7FFFFFFF), b)
    vals = cells_ref[...] + 1
    for g in range(L // LANES):
        at = (chunk * (L // LANES // F) + g // F, slice(g % F * R, (g % F + 1) * R))
        key_ref[at] = keys[:, g * LANES:(g + 1) * LANES]
        val_ref[at] = vals[:, g * LANES:(g + 1) * LANES]

    @pl.when(chunk == pl.num_programs(1) - 1)
    def _():
        _select_rows(nlive_ref, out_ref, words_ref, key_ref, val_ref, bits_ref,
                     k=k, words=words, R=R, W=slab)


def _select_rows(nlive_ref, out_ref, words_ref, key_ref, val_ref, bits_ref,
                 *, k, words, R, W):
    """Steps 2 to 5 over a block's keys and cells, once its last chunk is in."""
    i32 = jnp.int32
    F = SUBLANES // R
    V = key_ref.shape[0] - W  # registers in use; W more of holes behind them
    G, Cp = V * F, V * F * LANES
    N = W * SUBLANES  # a slab's rows
    lane = lax.broadcasted_iota(i32, (N, LANES), 1)
    row = lax.broadcasted_iota(i32, (N, LANES), 0)
    sub = row & (SUBLANES - 1)
    zero = jnp.zeros((N, LANES), i32)
    one = zero + 1
    first8 = lambda x: x[:SUBLANES]
    slabbed = lambda x: jnp.concatenate([x] * W, axis=0)  # a register's [8, 128] a slab over
    rows_of = lambda f: slice(f * R, (f + 1) * R)
    _put(key_ref, V, zero)  # past the end: holes
    _put(val_ref, V, zero)

    def turned(x, sh):  # each register's sublanes rolled by sh, cyclically
        if x.shape[0] == SUBLANES:
            return pltpu.roll(x, sh, 0)
        return lax.select(sub >= sh, pltpu.roll(x, sh, 0),
                          pltpu.roll(x, x.shape[0] + sh - SUBLANES, 0))

    def across(x):  # the sum over a row's F tiles of a register, at each of them
        sh = R
        while sh < SUBLANES:
            x = x + turned(x, sh)
            sh *= 2
        return x

    def before(x):  # the sum over a row's tiles of a register before each
        sh, tot = R, x
        while sh < SUBLANES:
            x = x + lax.select(sub >= sh, pltpu.roll(x, sh, 0), zero)
            sh *= 2
        return x - tot

    def ahead(a, a1, n):  # a's registers as n tiles further on (0 < n < F) would hold them
        return lax.select(sub < SUBLANES - n * R, pltpu.roll(a, N - n * R, 0),
                          pltpu.roll(a1, SUBLANES - n * R, 0))

    # -- 2. the k-th largest key: the largest t with count(key >= t) >= k
    def count(hit):  # [8, 128], a row's count in every lane of its sublanes
        def body(i, acc):
            ones = lax.select(hit(_grab(key_ref, i * W, W)), one, zero)
            return acc + jnp.sum(ones.reshape(W, SUBLANES, LANES), axis=0)

        acc = lax.fori_loop(0, V // W, body, first8(zero))
        return across(jnp.broadcast_to(
            jnp.sum(acc, axis=-1, keepdims=True), (SUBLANES, LANES)))

    def bit(i, t):  # t: the threshold's bits with the sign flipped (unsigned order)
        cand = t | lax.shift_left(i32(1), (31 - i).astype(i32))
        at_least = slabbed(cand ^ i32(_INT_MIN))
        n = count(lambda key: key >= at_least)
        return lax.select(n >= k, cand, t)

    tb = slabbed(lax.fori_loop(0, 32, bit, first8(zero)) ^ i32(_INT_MIN))
    room = slabbed(k - count(lambda key: key > tb))

    # -- 3. the tie rule by a prefix count; what each chosen position moves
    split = Cp < (1 << 16)  # both counts in one scan while they fit 16 bits
    # a position's place in its slab, + 1: register, tile of the register, lane
    tile_no = (lax.shift_right_logical(row, 3) * F
               + lax.shift_right_logical(sub, R.bit_length() - 1))
    place = tile_no * LANES + lane + 1
    past = [lane >= (1 << i) for i in range(7)]
    high, low, sixteen = zero + (1 << 16), zero + 0xFFFF, zero + 16

    def counted(x, carry):  # (inclusive prefix count in position order, next carry)
        for i, ok in enumerate(past):  # inside each tile, along its lanes
            x = x + lax.select(ok, pltpu.roll(x, 1 << i, 1), zero)
        tot = jnp.broadcast_to(x[:, LANES - 1:], (N, LANES))  # a tile's total
        x = x + before(tot)
        tot = across(tot)  # a register's, per row
        starts = []
        for u in range(W):  # from register to register of the slab
            starts.append(carry)
            carry = carry + tot[u * SUBLANES:(u + 1) * SUBLANES]
        return x + jnp.concatenate(starts, axis=0), carry

    def choose(i, carry):
        key, val = _grab(key_ref, i * W, W), _grab(val_ref, i * W, W)
        above, equal = key > tb, key == tb
        if split:
            p, carry = counted(lax.select(above, one, lax.select(equal, high, zero)), carry)
            n_above, n_equal = p & low, lax.shift_right_logical(p, sixteen)
        else:
            n_above, c0 = counted(lax.select(above, one, zero), carry[0])
            n_equal, c1 = counted(lax.select(equal, one, zero), carry[1])
            carry = (c0, c1)
        chosen = above | (equal & (n_equal <= room))
        rank = n_above + jnp.minimum(n_equal, room)  # chosen up to here, inclusive
        _put(key_ref, i * W, lax.select(chosen, i * (W * F * LANES) + (place - rank), zero))
        _put(val_ref, i * W, lax.select(chosen, val, zero))
        return carry

    lax.fori_loop(0, V // W, choose,
                  first8(zero) if split else (first8(zero), first8(zero)))

    # -- 4. the words, before anything moves: where a row's words are whole
    # tiles (WT of them), tile g is bit g // WT of the words' tile g % WT; else
    # a word tile's bits are windows of the row cut at any lane
    nlive = jnp.broadcast_to(nlive_ref[...], (R, LANES))
    lane_r = lane[:R]
    WT = words_ref.shape[1] // LANES

    def is_chosen(t, f):  # int32 0 / 1 [R, 128] of tile f of register t
        return ((val_ref[t, rows_of(f)] != 0)
                & ((t * F + f) * LANES + lane_r < nlive)).astype(i32)

    if words % LANES == 0:
        bits_ref[...] = jnp.zeros_like(bits_ref)

        def fill(t, _):
            for f in range(F):
                b = lax.div(t * F + f, i32(WT))
                j = t * F + f - b * WT
                bits_ref[j] = bits_ref[j] | lax.shift_left(is_chosen(t, f), b)
            return _

        lax.fori_loop(0, V, fill, 0)
        for j in range(WT):
            words_ref[:, j * LANES:(j + 1) * LANES] = bits_ref[j]
    else:
        def window(start):  # positions start .. start + 127
            g, sh = divmod(start, LANES)
            tile = lambda g: is_chosen(g // F, g % F) if g < G else zero[:R]
            if sh == 0:
                return tile(g)
            return lax.select(lane_r < LANES - sh,
                              pltpu.roll(tile(g), LANES - sh, 1),
                              pltpu.roll(tile(g + 1), LANES - sh, 1))

        for j in range(WT):
            w = zero[:R]
            for b in range(32):
                w = w | lax.shift_left(window(b * words + j * LANES), b)
            words_ref[:, j * LANES:(j + 1) * LANES] = w

    # -- 5. the compaction, a bit of the distance a stage, lowest first
    def settle(base, self_d, self_v, come_d, come_v, bit):
        take = (come_d & bit) != zero
        keep = (self_d & bit) == zero
        _put(key_ref, base, lax.select(take, come_d, lax.select(keep, self_d, zero)))
        _put(val_ref, base, lax.select(take, come_v, lax.select(keep, self_v, zero)))

    stages = (Cp - k).bit_length()
    whole = (F * LANES).bit_length() - 1  # from this bit on a move is whole registers

    def next_tiles(j, n):  # a move of n < F tiles: inside a register and into the next
        def some(i, _):
            d, v = _grab(key_ref, i * W, W + 1), _grab(val_ref, i * W, W + 1)
            settle(i * W, d[:N], v[:N], ahead(d[:N], d[SUBLANES:], n),
                   ahead(v[:N], v[SUBLANES:], n), zero + (1 << j))
            return _

        lax.fori_loop(0, V // W, some, 0)

    def next_tile(r):  # a slab and a register more, as one tile further on
        return r[SUBLANES:] if F == 1 else ahead(r[:N], r[SUBLANES:], 1)

    def by_lanes(j, _):  # a tile's upper lanes and the next tile's lower
        step = lax.shift_left(i32(1), j)
        back = LANES - step
        first = lane < back

        def some(i, _):
            d, v = _grab(key_ref, i * W, W + 1), _grab(val_ref, i * W, W + 1)
            rd, rv = pltpu.roll(d, back, 1), pltpu.roll(v, back, 1)
            settle(i * W, d[:N], v[:N], lax.select(first, rd[:N], next_tile(rd)),
                   lax.select(first, rv[:N], next_tile(rv)), zero + step)
            return _

        return lax.fori_loop(0, V // W, some, _)

    def by_registers(j, _):  # past the end come holes
        off = lax.shift_left(i32(1), j - whole)

        def some(i, _):
            come = jnp.minimum(i * W + off, V)
            settle(i * W, _grab(key_ref, i * W, W), _grab(val_ref, i * W, W),
                   _grab(key_ref, come, W), _grab(val_ref, come, W),
                   zero + lax.shift_left(i32(1), j))
            return _

        return lax.fori_loop(0, V // W, some, _)

    lax.fori_loop(0, min(stages, 7), by_lanes, 0)
    for j in range(7, min(stages, whole)):
        next_tiles(j, (1 << j) // LANES)
    lax.fori_loop(min(stages, whole), stages, by_registers, 0)

    n_out = out_ref.shape[1] // LANES
    packed = jnp.maximum(_grab(val_ref, 0, min(-(-n_out // F), V)) - 1, 0)
    for g in range(n_out):
        at = (g // F * SUBLANES + g % F * R)
        out_ref[:, g * LANES:(g + 1) * LANES] = (
            packed[at:at + R] if g < G else jnp.zeros((R, LANES), i32))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def dsa_select(scores: jax.Array,  # [B, C] f32, dead positions at -inf
               page_table: jax.Array,  # [B, MP] int32, C = MP * page size
               n_live: jax.Array,  # [B] int32: positions below it are live
               *, k: int, interpret: bool = False):
    """The k largest scores of each row (ties towards the lower position: the
    set `lax.top_k` takes) as (cells int32 [B, k], words int32 [B, W]): the
    pool's token cell `page_table[b, s // PS] * PS + s % PS` of each chosen
    position s, in position order, which puts the live ones first; and the
    chosen live positions as `models/mla.py` `pack_chosen` lays them out.
    Needs k <= C. No padding and no slice at 1, 2, 4 or a multiple of 8 rows
    and a C that is a multiple of 1024 with k and C / 32 multiples of 128
    (the cell's: 36,864 and 2048)."""
    B, C = scores.shape
    MP = page_table.shape[1]
    PS = C // MP
    assert MP * PS == C and 0 < k <= C, (scores.shape, page_table.shape, k)
    R = B if B in (1, 2, 4) else SUBLANES  # rows a block
    F = SUBLANES // R  # tiles of a row a register holds
    up = lambda n, m=LANES: -(-n // m) * m
    Bp, Cp, Kp, W = up(B, R), up(C, F * LANES), up(k), -(-C // 32)
    # every position's cell of the pool: the page table's entry over its slots
    cells = (page_table[:, :, None] * PS
             + jnp.arange(PS, dtype=jnp.int32)).reshape(B, C)
    scores, n_live = scores.astype(jnp.float32), n_live.astype(jnp.int32)
    if (Bp, Cp) != (B, C):
        scores = jnp.pad(scores, ((0, Bp - B), (0, Cp - C)), constant_values=-jnp.inf)
        cells = jnp.pad(cells, ((0, Bp - B), (0, Cp - C)))
        n_live = jnp.pad(n_live, (0, Bp - B))
    V = Cp // (F * LANES)  # registers a block
    slab = max(d for d in range(1, SUBLANES + 1) if V % d == 0)
    per = max(d for d in range(1, V + 1) if V % d == 0 and d * F <= 16)
    L = per * F * LANES  # a chunk: up to 16 tiles
    row = lambda width: pl.BlockSpec((R, width), lambda i, c: (i, 0))
    part = pl.BlockSpec((R, L), lambda i, c: (i, c))
    out, words = pl.pallas_call(
        functools.partial(_select_kernel, k=k, words=W, slab=slab),
        grid=(Bp // R, Cp // L),
        in_specs=[part, part, row(1)],
        out_specs=[row(Kp), row(up(W))],
        out_shape=[jax.ShapeDtypeStruct((Bp, Kp), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, up(W)), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((V + slab, SUBLANES, LANES), jnp.int32),
                        pltpu.VMEM((V + slab, SUBLANES, LANES), jnp.int32),
                        pltpu.VMEM((up(W) // LANES, R, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="dsa_select",
        interpret=interpret,
    )(scores, cells, n_live[:, None])
    return out[:B, :k], words[:B, :W]
