"""The selective state-space recurrence (Mamba-1) on the state pool.

    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * c_t) (x) B_t,    y_t = S_t C_t

`S` is `[N, d]` a sequence and a layer (N = d_state, d = the mixer's inner
width), float32. The pool holds one such state for every (state-space layer,
slot): `[Lm, slots, N, d // 128, 128]`, the channel axis split so that one
state row `S[n]` is whole vector registers (`[d // 128, 128]`: 40 sublanes at
d 5120) and `B_t[n]`, `C_t[n]` are scalars the kernels read from SMEM: the
update is then full-width multiply-adds and `exp`s, with no broadcast along
lanes and no reduction across sublanes. (`state_shape` gives the last two
axes; a width that is no multiple of 128 keeps `[1, d]`, the jnp forms only.)

Two operations, each as a Pallas kernel and as the plain `jnp` form that is
the CPU path and the parity oracle (scripts/tpu_parity.py, tests/test_jamba.py):

`ssm_update`  one token a row: the decode step. Rows 0 .. n_rows - 1 are live
  (a decode batch's real rows lead, engine/model_runner._stage_decode_rows);
  the grid is the live rows alone, each reading and writing its slot's block
  of the stacked pool in place (the pool is aliased to the output and blocked
  by a scalar-prefetched (layer, slot): the pipeline fetches row r + 1's state
  and writes row r - 1's back while row r computes). Padding rows run no
  grid step, move nothing and change no slot.
`ssm_scan`    the flat token axis of the ragged program (and of a prefill
  chunk), segment by segment: a token that starts a segment loads its slot's
  state (or zeroes it: the sequence's first token), every live token updates
  it, a token that ends a segment stores it. Per-token flags and slots are
  scalar-prefetched; tokens stream through VMEM in blocks of 8; the pool
  stays in HBM and is read and written by slot, in place.

A slot whose sequence starts (position 0) is never read: its old contents
cannot leak into a new sequence. Everything the kernels take is float32; the
state is stored in the pool's dtype (float32 as served).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TOKEN_BLOCK = 8

# per-token flags of ssm_scan (bits)
LIVE, LOAD, ZERO, STORE = 1, 2, 4, 8


def state_shape(d: int):
    """The last two axes of a state row `[d]` in the pool."""
    return (d // LANES, LANES) if d % LANES == 0 else (1, d)


def scan_flags(live, first, last, fresh):
    """Per-token int32 flags of ssm_scan from per-token booleans: `first` /
    `last` token of its segment, `fresh`: the segment starts its sequence."""
    first, last = live & first, live & last
    return (live * LIVE + (first & ~fresh) * LOAD + (first & fresh) * ZERO
            + last * STORE).astype(jnp.int32)


# --------------------------------------------------------------------------
# jnp forms
# --------------------------------------------------------------------------


def _step(S, dt, c, Bm, Cm, A):
    """One token: S [..., N, d], dt/c [..., d], Bm/Cm [..., N], A [N, d]."""
    S = jnp.exp(dt[..., None, :] * A) * S + (dt * c)[..., None, :] * Bm[..., :, None]
    return S, jnp.sum(S * Cm[..., :, None], axis=-2)


def ssm_update_jnp(pool, layer, slots, live, fresh, c, dt, Bm, Cm, A):
    """Rows of one token. pool [Lm, slots, N, dq, lanes]; slots [B] int32;
    live/fresh [B] bool; c, dt [B, d] f32; Bm, Cm [B, N] f32; A [N, d] f32.
    Returns (y [B, d] f32, pool); a row that is not live changes no slot
    and gives y = 0."""
    n_slots, N = pool.shape[1:3]
    d = c.shape[-1]
    S = pool[layer, slots].reshape(-1, N, d).astype(jnp.float32)
    S = jnp.where(fresh[:, None, None], 0.0, S)
    S, y = _step(S, dt, c, Bm, Cm, A)
    dst = jnp.where(live, slots, n_slots)  # out of bounds: dropped
    pool = pool.at[layer, dst].set(
        S.reshape((-1,) + pool.shape[2:]).astype(pool.dtype), mode="drop")
    return jnp.where(live[:, None], y, 0.0), pool


def ssm_scan_jnp(pool, layer, tok_slot, flags, c, dt, Bm, Cm, A):
    """The flat token axis. tok_slot, flags [T] int32 (scan_flags); c, dt
    [T, d]; Bm, Cm [T, N]. Returns (y [T, d] f32, pool)."""
    n_slots, N = pool.shape[1:3]
    T, d = c.shape
    stored = pool[layer, tok_slot].reshape(T, N, d).astype(jnp.float32)

    def body(S, xs):
        flag, S0, dt_t, c_t, b_t, c_out = xs
        S = jnp.where(flag & LOAD, S0, jnp.where(flag & ZERO, 0.0, S))
        S_new, y = _step(S, dt_t, c_t, b_t, c_out, A)
        live = (flag & LIVE) != 0
        S = jnp.where(live, S_new, S)
        return S, (jnp.where(live, y, 0.0), S)

    _, (y, after) = lax.scan(body, jnp.zeros((N, d), jnp.float32),
                             (flags, stored, dt, c, Bm, Cm))
    dst = jnp.where((flags & STORE) != 0, tok_slot, n_slots)
    pool = pool.at[layer, dst].set(
        after.reshape((T,) + pool.shape[2:]).astype(pool.dtype), mode="drop")
    return y, pool


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def _token(s_in, s_out, fresh, dt, c, b_at, c_at, a_ref, n_state: int):
    """One token's update of one state, row by row of the state: s_in /
    s_out are refs `[N, dq, lanes]` (they may be one ref), dt and c values
    `[dq, lanes]`, b_at(n) / c_at(n) scalars. Returns y `[dq, lanes]`."""
    u = dt * c
    y = jnp.zeros_like(dt)
    for n in range(n_state):
        s = s_in[n].astype(jnp.float32)
        if fresh is not None:
            s = jnp.where(fresh, 0.0, s)
        s = jnp.exp(dt * a_ref[n]) * s + u * b_at(n)
        s_out[n] = s.astype(s_out.dtype)
        y = y + s * c_at(n)
    return y


def _update_kernel(layer_ref, slot_ref, fresh_ref, c_ref, dt_ref, bc_ref,
                   a_ref, s_in, y_ref, s_out, *, n_state: int):
    del layer_ref, slot_ref  # consumed by the index maps
    r = pl.program_id(0)
    base = r * 2 * n_state
    y_ref[...] = _token(
        s_in, s_out, fresh_ref[r] != 0, dt_ref[...], c_ref[...],
        lambda n: bc_ref[base + n], lambda n: bc_ref[base + n_state + n],
        a_ref, n_state)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_update(pool, layer, slots, live, fresh, c, dt, Bm, Cm, A, *,
               interpret: bool = False):
    """ssm_update_jnp as a kernel; live rows must lead (see the module)."""
    N, dq, lanes = pool.shape[2:]
    B, d = c.shape
    n_rows = jnp.sum(live.astype(jnp.int32))
    to3 = lambda a: a.astype(jnp.float32).reshape(a.shape[0], dq, lanes)
    bc = jnp.concatenate([Bm, Cm], axis=-1).astype(jnp.float32).reshape(-1)
    row = pl.BlockSpec((None, dq, lanes), lambda r, ly, sl, fr: (r, 0, 0))
    state = pl.BlockSpec((None, None, N, dq, lanes),
                         lambda r, ly, sl, fr: (ly[0], sl[r], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, slots, fresh
        grid=(n_rows,),  # a traced bound: the live rows
        in_specs=[
            row, row,
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((N, dq, lanes), lambda r, *_: (0, 0, 0)),
            state,
        ],
        out_specs=[row, state],
    )
    y, pool = pl.pallas_call(
        functools.partial(_update_kernel, n_state=N),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, dq, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},  # pool (after 3 scalars, 4 inputs)
        interpret=interpret,
        name="ssm_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), to3(c), to3(dt), bc, A.reshape(N, dq, lanes),
      pool)
    # a row past the live ones ran no step: its block was never written
    return jnp.where(live[:, None], y.reshape(B, d), 0.0), pool


def _scan_kernel(layer_ref, slot_ref, flag_ref, c_ref, dt_ref, bc_ref, a_ref,
                 pool_in, y_ref, pool_ref, s_ref, buf_ref, sem, *,
                 n_state: int):
    del pool_in  # aliased to pool_ref
    blk = pl.program_id(0)
    layer = layer_ref[0]

    def token(i, carry):
        t = blk * TOKEN_BLOCK + i
        flag = flag_ref[t]
        at = pool_ref.at[layer, slot_ref[t]]

        @pl.when((flag & LOAD) != 0)
        def _load():
            cp = pltpu.make_async_copy(at, buf_ref, sem)
            cp.start()
            cp.wait()
            s_ref[...] = buf_ref[...].astype(jnp.float32)

        @pl.when((flag & ZERO) != 0)
        def _zero():
            s_ref[...] = jnp.zeros_like(s_ref)

        @pl.when((flag & LIVE) != 0)
        def _live():
            base = t * 2 * n_state
            y_ref[i] = _token(
                s_ref, s_ref, None, dt_ref[i], c_ref[i],
                lambda n: bc_ref[base + n],
                lambda n: bc_ref[base + n_state + n], a_ref, n_state)

        @pl.when((flag & LIVE) == 0)
        def _dead():
            y_ref[i] = jnp.zeros(y_ref.shape[1:], y_ref.dtype)

        @pl.when((flag & STORE) != 0)
        def _store():
            buf_ref[...] = s_ref[...].astype(buf_ref.dtype)
            cp = pltpu.make_async_copy(buf_ref, at, sem)
            cp.start()
            cp.wait()

        return carry

    lax.fori_loop(0, TOKEN_BLOCK, token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(pool, layer, tok_slot, flags, c, dt, Bm, Cm, A, *,
             interpret: bool = False):
    """ssm_scan_jnp as a kernel. T must be a multiple of TOKEN_BLOCK (every
    step program's token axis is)."""
    N, dq, lanes = pool.shape[2:]
    T, d = c.shape
    if T % TOKEN_BLOCK:
        raise ValueError(f"ssm_scan: {T} tokens, not a multiple of {TOKEN_BLOCK}")
    to3 = lambda a: a.astype(jnp.float32).reshape(T, dq, lanes)
    bc = jnp.concatenate([Bm, Cm], axis=-1).astype(jnp.float32).reshape(-1)
    toks = pl.BlockSpec((TOKEN_BLOCK, dq, lanes), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, tok_slot, flags
        grid=(T // TOKEN_BLOCK,),
        in_specs=[
            toks, toks,
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((N, dq, lanes), lambda b, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the pool: by slot, by hand
        ],
        out_specs=[toks, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((N, dq, lanes), jnp.float32),  # the running state
            pltpu.VMEM((N, dq, lanes), pool.dtype),  # in and out of the pool
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=N),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, dq, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_scan",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tok_slot.astype(jnp.int32),
      flags.astype(jnp.int32), to3(c), to3(dt), bc, A.reshape(N, dq, lanes),
      pool)
    return y.reshape(T, d), pool
