"""Pallas TPU routed experts for a forward of few rows (a decode step).

Off an expert mesh the routed FFN used to run every held expert over every
row and pick the routed terms out afterwards: a decode step streamed all
of the expert weights whatever its rows had picked (models/moe.py keeps
that path for everything this kernel does not take). Here a step reads an
expert's weights only if a real row picked it.

Grid: a WORK LIST of hit experts, `F // tf` grid steps each (one ffn tile
of the expert's gate, up and down matrices a step), its length a traced
bound, in the manner of ops/paged_attention.py's list of live pages.
`hit_work_list` builds it in XLA from the router's picks, the held range
and the rows' validity: the held experts that at least one REAL row
picked, ascending, and beside it a per-row weight column for every held
expert that is zero where the row did not pick it. A padding row puts
nothing on the list. Unlike the attention lists this one hangs on the
layer's own picks, so it is built inside the layer scan: a handful of
[T, k, n_held] compares and sums.

The weights are read in place: the operands are the layer-STACKED
matrices [L, n_held, E, F] / [L, n_held, F, E] as the parameters hold
them, indexed by (layer, expert, tile) from scalar-prefetched operands. A
Pallas call takes whole buffers, so handed the layer scan's slice
`we_gate[l]` XLA would copy that 0.5 GB slab out first, every layer of
every step (what PR 25 took out of the attention kernels).

No sort and no grouping of rows: every listed expert takes ALL T rows
(padded to the sublane tile) and the weight column zeroes the rows that
did not pick it. Gate, up, SiLU, the weighting, down and the sum over
experts are one pass with a resident [T, E] float32 accumulator (the
output block, whose index never changes). That is right while the call
stays bound by the weights it streams: a weight byte meets T flops (2
flops a parameter a row, 2 bytes a parameter), against the v5e's 240
flops a byte (197 TFLOP/s over 819 GB/s); the MXU holds a 128 x 128 tile
of WEIGHTS stationary and loading one costs about what 128 rows through
it do, so below 128 rows the product costs as if T were 128, still about
half of what the stream allows. `MAX_ROWS` = 32 keeps a factor of four
under that, and is where the list stops paying for a router of few
experts: at 32 rows of 4 picks a share of 32 held experts of 128 is hit
20 times in 32, at 64 rows 28 times. A WIDE router still leaves experts
unread there (8 picks of 512: 64 rows reach 63 % of the experts if they
pick alone, and the MXU's cost is the same at 64 rows as at 32), so
`row_bound` takes up to `WIDE_ROWS` = 64 rows where the rows' picks,
drawn evenly, would leave at least a quarter of the experts unread
(PERF.md section 6, PR 49: a 64-row decode bucket of ling-3.0-flash-vl on
the every-expert path read 32 held experts a layer where its rows hit 14).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import _div, _rem

# the row bounds of the all-rows-per-hit-expert form (module docstring)
MAX_ROWS = 32
WIDE_ROWS = 64
UNREAD_SHARE = 0.25


def row_bound(n_experts: int, k: int) -> int:
    """The most rows a forward may have to take the work-list kernel: from
    the router's width and its picks a token alone, both static."""
    unread = (1.0 - k / max(n_experts, 1)) ** WIDE_ROWS
    return WIDE_ROWS if unread >= UNREAD_SHARE else MAX_ROWS
# two buffers of a step's three weight tiles may take this much VMEM; the
# call asks for VMEM_LIMIT_BYTES of scoped VMEM (Mosaic's default is
# 16 MiB, a compiler default: a v5e core has 128 MiB)
TILE_BUDGET_BYTES = 24 * 1024 * 1024
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def ffn_tile(E: int, F: int, itemsize: int):
    """The ffn tile `tf` a grid step brings of each of an expert's three
    matrices ([E, tf], [E, tf], [tf, E]): the widest multiple of 128 lanes
    that divides F and keeps two buffers of the three inside
    TILE_BUDGET_BYTES, or F whole where F is no multiple of 128 (a block
    may span a whole axis). None: no legal tile fits, and the caller keeps
    the dense path."""
    def fits(tf):
        return 2 * 3 * E * tf * itemsize <= TILE_BUDGET_BYTES

    if F % 128:
        return F if fits(F) else None
    tiles = [tf for tf in range(F, 0, -128) if F % tf == 0 and fits(tf)]
    return tiles[0] if tiles else None


def hit_work_list(sel, weights, valid, expert_first: int, n_held: int):
    """The kernel's grid and its row weights, built in XLA from one
    layer's picks: (work[n_held] int32, n_work int32, wcol[T, n_held] f32).

    sel int32 [T, k] (ids over the router's full width), weights [T, k],
    valid bool [T] (False: a padding row, which lists nothing and weighs
    nothing). Entry w < n_work is the w-th held expert (an index into the
    held stack, ascending) that a real row picked; the entries past
    n_work repeat the last live one (they are never visited, and an index
    map may read one step ahead). wcol[t, e] is the weight row t gives
    held expert e, 0 where it did not pick it (a pick of an expert held
    elsewhere weighs nothing here). All of it compares and sums over
    [T, k, n_held] and [n_held, n_held]: no sort, no gather."""
    held = jnp.asarray(expert_first + np.arange(n_held), jnp.int32)
    onehot = (sel[..., None] == held) & valid[:, None, None]  # [T, k, n_held]
    wcol = jnp.sum(jnp.where(onehot, weights[..., None], 0)
                   .astype(jnp.float32), axis=1)
    hit = jnp.any(onehot, axis=(0, 1))  # [n_held]
    e = lax.iota(jnp.int32, n_held)
    # place[e] = how many hit experts lie below e: its slot in the list
    place = jnp.sum(hit[None, :] & (e[None, :] < e[:, None]), axis=1,
                    dtype=jnp.int32)
    n_work = jnp.sum(hit, dtype=jnp.int32)
    # entry w = the hit expert placed at w = the largest placed at or
    # below w, which past the live entries is the last live one
    work = jnp.max(
        jnp.where(hit[None, :] & (place[None, :] <= e[:, None]),
                  e[None, :], 0), axis=1)
    return work, n_work, wcol


def _routed_experts_kernel(
    work_ref,  # [n_held] int32 (SMEM): the hit experts, ascending
    ly_ref,  # [1] int32: the layer of the stacks (read by the index maps)
    x_ref,  # [Tp, E] the rows, padded to the sublane tile
    wcol_ref,  # [Tp, n_held] f32 row weights a held expert
    wg_ref,  # [E, tf] this step's tile of the expert's gate matrix
    wu_ref,  # [E, tf] ... of its up matrix
    wd_ref,  # [tf, E] ... of its down matrix
    o_ref,  # [Tp, E] f32: the sum over experts and tiles, resident
    *,
    n_tiles: int,
):
    del ly_ref
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    e = work_ref[_div(s, n_tiles)]
    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    wcol = wcol_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, wcol.shape, 1)
    col = jnp.sum(jnp.where(lane == e, wcol, 0.0), axis=1, keepdims=True)
    # a row that did not pick this expert adds exactly nothing, whatever
    # the expert would have made of it (0 x inf is not 0)
    act = jnp.where(col != 0.0, jax.nn.silu(gate) * up * col, 0.0)
    o_ref[...] += jnp.dot(act.astype(wd_ref.dtype), wd_ref[...],
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def routed_experts(
    x: jax.Array,  # [T, E]
    work: jax.Array,  # hit_work_list's (work, n_work, wcol)
    n_work: jax.Array,
    wcol: jax.Array,  # [T, n_held] f32
    we_gate: jax.Array,  # [L, n_held, E, F], every layer's
    we_up: jax.Array,
    we_down: jax.Array,  # [L, n_held, F, E]
    layer,  # traced int32 scalar: the layer of the stacks to read
    *,
    tile=None,  # static ffn tile override (scripts/bench_moe.py)
    interpret: bool = False,
) -> jax.Array:
    """sum over the listed experts e of wcol[:, e] * (silu(x @ gate_e) *
    (x @ up_e)) @ down_e, as float32 [T, E]. Products accumulate in
    float32 as `mm`'s do; the activation goes to the down product in the
    weights' dtype. With nothing listed the result is exactly 0."""
    T, E = x.shape
    _, n_held, _, F = we_gate.shape
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    tf = tile or ffn_tile(E, F, we_gate.dtype.itemsize)
    if tf is None:
        raise ValueError(f"no ffn tile of [{E}, {F}] experts fits VMEM")
    n_tiles = F // tf
    # rows to the sublane tile of the narrowest operand (bf16: 16)
    sub = 32 // min(x.dtype.itemsize, 4)
    Tp = -(-T // sub) * sub
    x = jnp.pad(x, ((0, Tp - T), (0, 0)))
    wcol = jnp.pad(wcol, ((0, Tp - T), (0, 0)))

    def gate_index(s, wk, ly):
        return (ly[0], wk[_div(s, n_tiles)], 0, _rem(s, n_tiles))

    def down_index(s, wk, ly):
        return (ly[0], wk[_div(s, n_tiles)], _rem(s, n_tiles), 0)

    def whole(s, *_):
        return (0, 0)

    up_spec = pl.BlockSpec((None, None, E, tf), gate_index)
    n_work = jnp.asarray(n_work, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # work, layer
        grid=(n_work * n_tiles,),  # a traced bound: the hit experts' tiles
        in_specs=[
            pl.BlockSpec((Tp, E), whole),
            pl.BlockSpec((Tp, n_held), whole),
            up_spec,
            up_spec,
            pl.BlockSpec((None, None, tf, E), down_index),
        ],
        out_specs=pl.BlockSpec((Tp, E), whole),
    )
    out = pl.pallas_call(
        functools.partial(_routed_experts_kernel, n_tiles=n_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, E), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="routed_experts",
    )(work, layer, x, wcol, we_gate, we_up, we_down)
    # with nothing listed no step runs and the block is never written
    return jnp.where(n_work > 0, out[:T], 0.0)
