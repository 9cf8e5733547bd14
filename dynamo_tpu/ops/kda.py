"""Kimi delta attention (KDA; Kimi Linear, arXiv:2510.26692) on the state pool:
a delta rule with a decay a channel.

    S' = alpha_t[:, None] * S_{t-1}                    alpha_t = exp(g_t), [d_k]
    S_t = S' + beta_t k_t (v_t - k_t^T S')^T           S [d_k, d_v] a head, f32
    o_t = S_t^T q_t

The rank-one correction reads the state before it writes it. The pool holds
one such state for every (KDA layer, slot, head): `[Lk, slots, H, d_k, d_v]`,
float32, a head's state one `[d_k, d_v]` tile with the values on the lanes.

Three forms:

`kda_recurrence`  token by token in float32 (`lax.scan`): the oracle of the
  other two (tests/test_kda.py, scripts/tpu_parity.py).
`kda_update`  one token a row, the decode step, as a Pallas kernel and as the
  plain `jnp` form that is the CPU path. Rows 0 .. n_rows - 1 are live (a
  decode batch's real rows lead); the grid is (live rows, blocks of heads),
  each step reading and writing its slot's block of the stacked pool in place
  (aliased, blocked by a scalar-prefetched (layer, slot), as ops/ssm.py's
  update is). Padding rows run no grid step and change no slot. It streams
  the state once in and once out and is bound by that.
`kda_chunk`  one segment of T tokens (a prefill chunk) from a carried-in
  state, chunkwise: blocks of `BLOCK` = 64 tokens in the WY form of the delta
  rule. With the cumulative log-decay `G` a channel inside a block, the
  pseudo-values `W` solve `(I + B tril(A, -1)) W = B (V - (K * e^G) S_0)`
  with `A[t, s] = sum_c k_t[c] k_s[c] e^(G_t[c] - G_s[c])`; then
  `o = (Q * e^G) S_0 + tril(Aq) W` and `S_C = e^(G_C) * S_0 + (K * e^(G_C -
  G))^T W`. Everything that does not hang on the carried state (A, Aq, the
  inverse, `U = T B V`, `Wk = T B (K e^G)`) is computed for all blocks at once
  by XLA matmuls; what does (`W = U - Wk S`, `o`, the state's step) runs
  block after block with a head's state resident in VMEM: the Pallas kernel
  `kda_chunk`, or a `lax.scan` on the CPU path.

  The decay is a channel's, so `e^(G_t - G_s)` does not factor into a safe
  `e^(G_t) e^(-G_s)` over a whole block: with `g >= -5` a run of n tokens
  is bounded by e^(5 n), and float32 holds e^88. So `A` and `Aq` are built in
  sub-blocks of `SUB` = 16 tokens (5 x 16 = 80 < 88), each row block against
  the reference point `R_i` = `G` at its middle token: `e^(G_t - R_i)` and,
  inside the row's own sub-block, `e^(R_i - G_s)` lie in [e^-40, e^40]; for an
  earlier sub-block's s the second is at most 1 (it may underflow, as the true
  product does). Nothing else takes a positive exponent. A padding token is
  the identity (beta 0, g 0: the caller's to set).

Every operand is float32 and every product runs at `highest` precision: the
state carries a sequence's whole past, and a decay rounded to 8 bits of
mantissa compounds (a bf16 state is the mechanism control that `correct`
refuses: benchmark/configs/ling-3.0-flash-vl.json).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 64  # tokens of one WY block
SUB = 16  # tokens that share a reference point: 5 x 16 < 88
HEAD_BLOCK = 16  # heads of one grid step of kda_update (1 MiB of state)
VEC_ROWS = 8  # rows of a head's operand tile: alpha, k, q, v, beta, 0, 0, 0
_HI = lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# the oracle
# --------------------------------------------------------------------------


def _step(S, q, k, v, g, beta):
    """One token of any batch of heads: S [..., dk, dv], q/k/g [..., dk],
    v [..., dv], beta [...]. Returns (S_t, o_t)."""
    S = jnp.exp(g)[..., None] * S
    u = v - jnp.sum(k[..., None] * S, axis=-2)
    S = S + k[..., None] * (beta[..., None] * u)[..., None, :]
    return S, jnp.sum(q[..., None] * S, axis=-2)


def kda_recurrence(S0, q, k, v, g, beta):
    """The recurrence token by token: S0 [H, dk, dv]; q, k, g [T, H, dk];
    v [T, H, dv]; beta [T, H]; all float32. Returns (o [T, H, dv], S_T)."""
    S, o = lax.scan(lambda S, xs: _step(S, *xs), S0.astype(jnp.float32),
                    (q, k, v, g, beta))
    return o, S


# --------------------------------------------------------------------------
# one token a row (the decode step)
# --------------------------------------------------------------------------


def kda_update_jnp(pool, layer, slots, live, fresh, q, k, v, g, beta):
    """Rows of one token. pool [Lk, slots, H, dk, dv] f32; slots [B] int32;
    live/fresh [B] bool; q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H]; f32.
    Returns (o [B, H, dv] f32, pool); a row that is not live changes no slot
    and gives o = 0."""
    n_slots = pool.shape[1]
    S = pool[layer, slots].astype(jnp.float32)
    S = jnp.where(fresh[:, None, None, None], 0.0, S)
    S, o = _step(S, q, k, v, g, beta)
    dst = jnp.where(live, slots, n_slots)  # out of bounds: dropped
    pool = pool.at[layer, dst].set(S.astype(pool.dtype), mode="drop")
    return jnp.where(live[:, None, None], o, 0.0), pool


def _col(row, eye):
    """A row [1, d] as a column [d, 1]: the diagonal of its broadcast, summed
    along the lanes (exact: one term a row; no MXU pass rounds it)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _eye(d: int):
    return (lax.broadcasted_iota(jnp.int32, (d, d), 0)
            == lax.broadcasted_iota(jnp.int32, (d, d), 1))


def _update_kernel(layer_ref, slot_ref, fresh_ref, vec_ref, s_in, o_ref,
                   s_out, *, heads: int):
    del layer_ref, slot_ref  # consumed by the index maps
    fresh = fresh_ref[pl.program_id(0)] != 0
    d = s_in.shape[-1]
    eye = _eye(d)

    def head(h, carry):
        t = vec_ref[h]  # [8, d]: alpha, k, q, v, beta (broadcast), zeros
        S = jnp.where(fresh, 0.0, s_in[h].astype(jnp.float32))
        k_col = _col(t[1:2], eye)
        S = _col(t[0:1], eye) * S
        u = t[3:4] - jnp.sum(k_col * S, axis=0, keepdims=True)  # [1, dv]
        S = S + k_col * (t[4:5] * u)
        s_out[h] = S.astype(s_out.dtype)
        o_ref[pl.ds(h, 1), :] = jnp.sum(_col(t[2:3], eye) * S, axis=0,
                                        keepdims=True)
        return carry

    lax.fori_loop(0, heads, head, 0)


def head_block(H: int) -> int:
    return max(b for b in range(1, min(HEAD_BLOCK, H) + 1) if H % b == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_update(pool, layer, slots, live, fresh, q, k, v, g, beta, *,
               interpret: bool = False):
    """kda_update_jnp as a kernel; live rows must lead (see the module) and
    d_k = d_v (one tile holds a head's operands)."""
    H, dk, dv = pool.shape[2:]
    B = q.shape[0]
    if dk != dv:
        raise ValueError(f"kda_update: d_k {dk} != d_v {dv}")
    hb = head_block(H)
    n_rows = jnp.sum(live.astype(jnp.int32))
    f32 = lambda a: a.astype(jnp.float32)
    vec = jnp.stack(
        [jnp.exp(f32(g)), f32(k), f32(q), f32(v),
         jnp.broadcast_to(f32(beta)[..., None], (B, H, dk))]
        + [jnp.zeros((B, H, dk), jnp.float32)] * (VEC_ROWS - 5), axis=2)
    state = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda r, j, ly, sl, fr: (ly[0], sl[r], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, slots, fresh
        grid=(n_rows, H // hb),  # a traced bound: the live rows
        in_specs=[
            pl.BlockSpec((None, hb, VEC_ROWS, dk),
                         lambda r, j, *_: (r, j, 0, 0)),
            state,
        ],
        out_specs=[pl.BlockSpec((None, hb, dv), lambda r, j, *_: (r, j, 0)),
                   state],
    )
    o, pool = pl.pallas_call(
        functools.partial(_update_kernel, heads=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1},  # pool (after 3 scalars, 1 input)
        interpret=interpret,
        name="kda_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), vec, pool)
    # a row past the live ones ran no step: its block was never written
    return jnp.where(live[:, None, None], o, 0.0), pool


# --------------------------------------------------------------------------
# a segment of tokens (the prefill chunk)
# --------------------------------------------------------------------------


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def _chunk_parts(q, k, v, g, beta):
    """What the blocks' walk reads, for all blocks at once. q, k, g
    [T, H, dk], v [T, H, dv], beta [T, H], float32, T a multiple of BLOCK.
    Returns, each `[H, nb, ...]`: U [C, dv], Wk [C, dk], khat [C, dk],
    qbar [C, dk], Aq [C, C] (lower triangle), gamma [dk] (the block's whole
    decay)."""
    T, H, dk = k.shape
    C, nb, ns = BLOCK, T // BLOCK, BLOCK // SUB
    hb = lambda a: jnp.moveaxis(a.reshape(nb, C, H, -1), 2, 0)  # [H, nb, C, .]
    q, k, v, g, beta = hb(q), hb(k), hb(v), hb(g), hb(beta)
    # the cumulative log-decay, a token's own step included, summed inside
    # its sub-block first: a running sum over the whole block rounds at the
    # size of the block's total (320 at the bound), and the decay between two
    # neighbours is a difference of two such sums
    gl = jnp.cumsum(g.reshape(H, nb, ns, SUB, dk), axis=3)
    R = jnp.cumsum(gl[:, :, :, -1], axis=2) - gl[:, :, :, -1]  # G before a sub-block
    G = (R[:, :, :, None] + gl).reshape(H, nb, C, dk)
    # a sub-block's reference point is its MIDDLE token: a row's factor and a
    # source's then lie in [e^-40, e^40] inside it (against its first token a
    # row's would reach e^-80, where a small component of q is denormal)
    mid = gl[:, :, :, SUB // 2 - 1]  # [H, nb, ns, dk]
    read = jnp.exp(gl - mid[:, :, :, None])
    kr = k.reshape(H, nb, ns, SUB, dk) * read
    qr = q.reshape(H, nb, ns, SUB, dk) * read
    rows_k, rows_q = [], []
    for i in range(ns):  # row block i against the tokens up to its last
        n = (i + 1) * SUB
        expo = ((R[:, :, i, None] + mid[:, :, i, None]
                 - R[:, :, :i + 1])[:, :, :, None]
                - gl[:, :, :i + 1]).reshape(H, nb, n, dk)
        kw = k[:, :, :n] * jnp.exp(expo)
        pad = ((0, 0), (0, 0), (0, 0), (0, C - n))
        rows_k.append(jnp.pad(_mm("hbtc,hbsc->hbts", kr[:, :, i], kw), pad))
        rows_q.append(jnp.pad(_mm("hbtc,hbsc->hbts", qr[:, :, i], kw), pad))
    t = jnp.arange(C)
    Ak = jnp.where(t[:, None] > t[None, :], jnp.concatenate(rows_k, axis=2), 0.0)
    Aq = jnp.where(t[:, None] >= t[None, :], jnp.concatenate(rows_q, axis=2), 0.0)
    Gam = jnp.exp(G)
    gc = G[:, :, -1:]  # [H, nb, 1, dk]
    # (I + N) X = B [V, K e^G], N = B tril(A, -1): forward substitution a
    # sub-block at a time, the diagonal blocks inverted row by row. (The
    # product form (I - N)(I + N^2)(I + N^4)... is exact on paper and useless
    # here: keys behind a SiLU share a direction, every entry of N is then a
    # few tenths, N^32 has entries of 1e8 and the float32 sum of the series
    # cancels to noise or overflows: PERF.md section 6, PR 49.)
    N = (beta * Ak).reshape(H, nb, ns, SUB, ns, SUB)
    rhs = (beta * jnp.concatenate([v, k * Gam], axis=-1)).reshape(
        H, nb, ns, SUB, -1)
    X = []
    for i in range(ns):
        b = rhs[:, :, i]
        for j in range(i):
            b = b - _mm("hbts,hbsd->hbtd", N[:, :, i, :, j], X[j])
        X.append(_mm("hbts,hbsd->hbtd",
                     _unit_lower_inverse(N[:, :, i, :, i]), b))
    X = jnp.stack(X, axis=2).reshape(H, nb, C, -1)
    U, Wk = X[..., :v.shape[-1]], X[..., v.shape[-1]:]
    return U, Wk, k * jnp.exp(gc - G), q * Gam, Aq, jnp.exp(gc[:, :, 0])


def _unit_lower_inverse(N):
    """(I + N)^-1 for strictly lower triangular N [..., n, n], row by row:
    with X = I + A, row i of A is -N[i] - sum_{k<i} N[i, k] A[k] (forward
    substitution: every entry stays of the size of the answer's)."""
    n = N.shape[-1]
    A = -N
    below = jnp.arange(n)
    for i in range(1, n):
        row = A[..., i, :]
        new = row + _mm("...k,...kj->...j", row, A)  # row[k] is 0 for k >= i
        A = A.at[..., i, :].set(jnp.where(below < i, new, row))
    return A + jnp.eye(n, dtype=N.dtype)


def _pad_tokens(T: int, *arrays):
    pad = -T % BLOCK
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def _chunk_out(o, T: int):
    """[H, nb, C, dv] -> [T, H, dv]."""
    H, nb, C, dv = o.shape
    return jnp.moveaxis(o, 0, 2).reshape(nb * C, H, dv)[:T]


def kda_chunk_jnp(S0, q, k, v, g, beta):
    """kda_recurrence chunkwise, the blocks' walk a `lax.scan` (the CPU path
    and the kernel's oracle at block size)."""
    T = q.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    parts = _chunk_parts(*_pad_tokens(T, f32(q), f32(k), f32(v), f32(g), f32(beta)))

    def block(S, xs):
        U, Wk, khat, qbar, Aq, gam = xs  # [H, ...]
        W = U - _mm("htc,hcd->htd", Wk, S)
        o = _mm("htc,hcd->htd", qbar, S) + _mm("hts,hsd->htd", Aq, W)
        return gam[..., None] * S + _mm("htc,htd->hcd", khat, W), o

    S, o = lax.scan(block, f32(S0), tuple(jnp.moveaxis(a, 1, 0) for a in parts))
    return _chunk_out(jnp.moveaxis(o, 0, 1), T), S


def _chunk_kernel(u_ref, wk_ref, kht_ref, qb_ref, aq_ref, gam_ref, s_in,
                  o_ref, s_out, s_scr):
    b = pl.program_id(1)
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)

    @pl.when(b == 0)
    def _load():
        s_scr[...] = s_in[...].astype(jnp.float32)

    S = s_scr[...]
    W = u_ref[...] - dot(wk_ref[...], S)
    o_ref[...] = dot(qb_ref[...], S) + dot(aq_ref[...], W)
    S = _col(gam_ref[0:1], _eye(S.shape[0])) * S + dot(kht_ref[...], W)
    s_scr[...] = S

    @pl.when(b == pl.num_programs(1) - 1)
    def _store():
        s_out[...] = S.astype(s_out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk(S0, q, k, v, g, beta, *, interpret: bool = False):
    """kda_chunk_jnp with the blocks' walk as a kernel: grid (heads, blocks),
    a head's state in VMEM from its first block to its last."""
    T = q.shape[0]
    H, dk, dv = S0.shape
    f32 = lambda a: a.astype(jnp.float32)
    U, Wk, khat, qbar, Aq, gam = _chunk_parts(
        *_pad_tokens(T, f32(q), f32(k), f32(v), f32(g), f32(beta)))
    nb, C = U.shape[1], BLOCK
    gam = jnp.broadcast_to(gam[:, :, None], (H, nb, VEC_ROWS, dk))
    blk = lambda *tail: pl.BlockSpec((None, None) + tail,
                                     lambda h, b: (h, b, 0, 0))
    state = pl.BlockSpec((None, dk, dv), lambda h, b: (h, 0, 0))
    o, S = pl.pallas_call(
        _chunk_kernel,
        grid=(H, nb),
        in_specs=[blk(C, dv), blk(C, dk), blk(dk, C), blk(C, dk), blk(C, C),
                  blk(VEC_ROWS, dk), state],
        out_specs=[blk(C, dv), state],
        out_shape=[jax.ShapeDtypeStruct((H, nb, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(U, Wk, jnp.swapaxes(khat, 2, 3), qbar, Aq, gam, f32(S0))
    return _chunk_out(o, T), S
