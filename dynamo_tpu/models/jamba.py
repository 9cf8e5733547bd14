"""Jamba's hybrid decoder: Mamba-1 selective state-space mixers with an
attention layer every `attn_layer_period` (at `attn_layer_offset`), a dense
SwiGLU MLP in every layer, tied embedding (`model_type: jamba` with
`num_experts` 1; the equations are benchmark/reference/jamba_decoder.py's).

What differs from models/llama.py, and why this is a forward of its own:
the layers are not alike, so the walk is not one `lax.scan` over a stack.
Whole periods are one scan over the period's index; inside it the runs of
Mamba layers before and after the period's attention layer are each a scan
over layer indices (a body reads its layer of the stacked `mamba`, `attn` and
`layers` trees in place): two Mamba bodies and one attention layer compiled,
however deep the model. Layers past the last whole period are written out. An attention layer's
index into the KV pool is its rank among attention layers (the pool holds
`config.kv_layers` of them), no position term enters its scores.

A sequence carries, besides its pages, one **state slot**: for every Mamba
layer the recurrent state `S` (float32) and the last `d_conv - 1` inputs
of the causal convolution. `make_state_pool` holds them for `slots`
sequences; slot 0 is scratch as page 0 is (a call that names no slot uses
it, and no sequence owns it). Every step program takes the pool and, as
data, where each of its rows or segments keeps its state, and returns the
pool (donated: updated in place). A sequence's first token (position 0)
starts from zeros whatever its slot held, so slots need no clearing.

Parameter tree: embed [V, E], norm_f [E], layers.{attn_norm, mlp_norm
[L, E]; w_gate, w_up, w_down [L, in, out]} (every layer), mamba.{...}
[Lm, ...] and attn.{wq, wk, wv, wo} [La, in, out] in model order. Every
drawn matrix is `[..., in, out]` (the convolution `[K, d]`: fan-in K);
A_log, D, b_dt, b_conv and the norm weights are fills (benchmark/serve.py
draws what init_params draws from its key and keeps what it fills).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import embed_lookup, mm, tied_logits
from dynamo_tpu.models.toolkit import (
    Params,
    SideCacheOps,
    _write_kv,
    paged_attention_jnp,
    rms_norm,
)
from dynamo_tpu.ops import ssm as ssm_ops


# --------------------------------------------------------------------------
# init + state pool
# --------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    c = config
    L, Lm, La = c.n_layers, c.mamba_layers, c.kv_layers
    E, d, N, R, K = c.dim, c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
    hd = c.head_dim
    k = jax.random.split(key, 13)

    def w(kk, fan_in, *shape):
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    # the published initialisation: A = -(1 .. N) on every channel, D = 1,
    # b_dt the inverse softplus of steps spaced evenly in log over the
    # channels from 0.001 to 0.1
    steps = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), d))
    b_dt = np.log(np.expm1(steps))
    return {
        "embed": w(k[0], E, c.vocab_size, E),
        "norm_f": ones(E),
        "layers": {
            "attn_norm": ones(L, E),
            "mlp_norm": ones(L, E),
            "w_gate": w(k[1], E, L, E, c.ffn_dim),
            "w_up": w(k[2], E, L, E, c.ffn_dim),
            "w_down": w(k[3], c.ffn_dim, L, c.ffn_dim, E),
        },
        "mamba": {
            "w_in": w(k[4], E, Lm, E, 2 * d),
            "w_conv": w(k[5], K, Lm, K, d),
            "b_conv": jnp.zeros((Lm, d), jnp.float32),
            "w_x": w(k[6], d, Lm, d, R + 2 * N),
            "dt_norm": ones(Lm, R),
            "b_norm": ones(Lm, N),
            "c_norm": ones(Lm, N),
            "w_dt": w(k[7], R, Lm, R, d),
            "b_dt": jnp.broadcast_to(jnp.asarray(b_dt, jnp.float32), (Lm, d)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (Lm, N, d)),
            "D": ones(Lm, d),
            "w_out": w(k[8], d, Lm, d, E),
        },
        "attn": {
            "wq": w(k[9], E, La, E, c.n_heads * hd),
            "wk": w(k[10], E, La, E, c.n_kv_heads * hd),
            "wv": w(k[11], E, La, E, c.n_kv_heads * hd),
            "wo": w(k[12], c.n_heads * hd, La, c.n_heads * hd, E),
        },
    }


def state_slot_bytes(config: ModelConfig, conv_dtype=jnp.bfloat16) -> int:
    """Bytes of one sequence's recurrent state over all Mamba layers (`S`
    in float32)."""
    c = config
    d = c.mamba_d_inner
    return c.mamba_layers * (
        c.mamba_d_state * d * 4
        + (c.mamba_d_conv - 1) * d * jnp.dtype(conv_dtype).itemsize)


def make_state_pool(config: ModelConfig, slots: int, state_dtype=jnp.float32,
                    conv_dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """{"S": [Lm, slots, N, d // 128, 128], "conv": [Lm, slots, K - 1, d]}:
    zeros. The channel axis of `S` is split as ops/ssm.py's kernels read it."""
    c = config
    d = c.mamba_d_inner
    return {
        "S": jnp.zeros((c.mamba_layers, slots, c.mamba_d_state)
                       + ssm_ops.state_shape(d), state_dtype),
        "conv": jnp.zeros((c.mamba_layers, slots, c.mamba_d_conv - 1, d),
                          conv_dtype),
    }


# --------------------------------------------------------------------------
# where each token's state lives
# --------------------------------------------------------------------------


class _Plan(NamedTuple):
    """A forward's state bookkeeping, built once and read by every Mamba
    layer. `rows`: one token a row (the decode step), else the flat token
    axis in segments."""
    rows: bool
    live: jax.Array  # [T] bool: a real token
    fresh: jax.Array  # [T] bool: its segment starts its sequence
    tok_slot: jax.Array  # [T] int32
    # segments (flat form only)
    off: Optional[jax.Array] = None  # [T] offset of the token in its segment
    seg_of: Optional[jax.Array] = None  # [T]
    seg_slot: Optional[jax.Array] = None  # [SEG]
    seg_start: Optional[jax.Array] = None  # [SEG]
    seg_len: Optional[jax.Array] = None  # [SEG] (0: a dead entry)
    seg_fresh: Optional[jax.Array] = None  # [SEG] bool
    flags: Optional[jax.Array] = None  # [T] ssm_ops.scan_flags


def _plan(positions: jax.Array, slots: jax.Array, ragged: bool) -> _Plan:
    B, S = positions.shape
    if S == 1 and not ragged:
        pos = positions[:, 0]
        return _Plan(True, pos >= 0, pos == 0, slots.astype(jnp.int32))
    if B != 1:
        raise NotImplementedError(
            "a state-space model takes prefill chunks one at a time or as "
            "segments of the flat ragged step, never as a padded [N, S] pack")
    pos = positions[0]
    T = pos.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    if ragged:
        seg_slot, seg_start, seg_len = (slots[i].astype(jnp.int32) for i in range(3))
    else:
        seg_slot = slots.astype(jnp.int32).reshape(1)
        seg_start = jnp.zeros(1, jnp.int32)
        seg_len = jnp.sum((pos >= 0).astype(jnp.int32)).reshape(1)
    # segments lie in order on the token axis; a dead entry starts past it
    start = jnp.where(seg_len > 0, seg_start, T)
    seg_of = jnp.maximum(
        jnp.sum((t[:, None] >= start[None, :]).astype(jnp.int32), axis=1) - 1, 0)
    off = t - seg_start[seg_of]
    live = (pos >= 0) & (off < seg_len[seg_of])
    seg_fresh = pos[jnp.clip(seg_start, 0, T - 1)] == 0
    fresh = seg_fresh[seg_of]
    flags = ssm_ops.scan_flags(live, off == 0, off == seg_len[seg_of] - 1, fresh)
    return _Plan(False, live, fresh, seg_slot[seg_of], off, seg_of, seg_slot,
                 seg_start, seg_len, seg_fresh, flags)


# --------------------------------------------------------------------------
# the Mamba mixer
# --------------------------------------------------------------------------


def _rms32(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _conv(c: ModelConfig, mp, a, plan: _Plan, conv_pool, m_idx):
    """The causal depthwise convolution over each sequence's inputs, the
    first K - 1 of a segment reaching into its slot's stored ones. a [T, d].
    Returns (pre-activation [T, d] f32, conv_pool with every live row's or
    segment's last K - 1 inputs stored)."""
    K = c.mamba_d_conv
    n_slots = conv_pool.shape[1]
    T, d = a.shape
    w = mp["w_conv"].astype(jnp.float32)  # [K, d]
    af = a.astype(jnp.float32)
    if plan.rows:
        prev = conv_pool[m_idx, plan.tok_slot]  # [T, K-1, d]
        prev = jnp.where(plan.fresh[:, None, None], 0, prev)
        window = jnp.concatenate([prev, a[:, None].astype(prev.dtype)], axis=1)
        out = mp["b_conv"] + jnp.einsum("tkd,kd->td", window.astype(jnp.float32), w)
        dst = jnp.where(plan.live, plan.tok_slot, n_slots)
        return out, conv_pool.at[m_idx, dst].set(window[:, 1:], mode="drop")
    SEG = plan.seg_slot.shape[0]
    prev = conv_pool[m_idx, plan.seg_slot]  # [SEG, K-1, d]
    prev = jnp.where(plan.seg_fresh[:, None, None], 0, prev)
    prev_flat = prev.reshape(SEG * (K - 1), d)
    t = jnp.arange(T, dtype=jnp.int32)
    out = mp["b_conv"] + w[K - 1] * af
    for k in range(1, K):
        hist = prev_flat[plan.seg_of * (K - 1) + jnp.clip(K - 1 + plan.off - k, 0, K - 2)]
        src = jnp.where((plan.off >= k)[:, None], a[jnp.maximum(t - k, 0)], hist)
        out = out + w[K - 1 - k] * src.astype(jnp.float32)
    # each segment's last K - 1 inputs: its own tokens, and where it has
    # fewer, what its slot held before them
    rows = []
    for j in range(K - 1):
        p = plan.seg_len - (K - 1) + j
        own = a[jnp.clip(plan.seg_start + p, 0, T - 1)]
        old = jnp.take_along_axis(
            prev, jnp.clip(K - 1 + p, 0, K - 2)[:, None, None], axis=1)[:, 0]
        rows.append(jnp.where((p >= 0)[:, None], own.astype(prev.dtype), old))
    dst = jnp.where(plan.seg_len > 0, plan.seg_slot, n_slots)
    return out, conv_pool.at[m_idx, dst].set(jnp.stack(rows, axis=1), mode="drop")


def _mamba_mixer(c: ModelConfig, mp, x, plan: _Plan, state, m_idx, impl: str,
                 inner_norms: bool = True, scan_out: bool = False):
    """x [T, E] (normed) -> ([T, E], state). Without `inner_norms` the step,
    B and C go on as projected (Mamba-1 as published; the three norms are
    Jamba's); with `scan_out` the scan's output `y + D * c` [T, d] f32,
    before the mixer's own gate, follows the state (models/sambay.py)."""
    d, N, R = c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank
    with jax.named_scope("ssm.proj"):
        az = mm(x, mp["w_in"])
        a, z = az[:, :d], az[:, d:]
    with jax.named_scope("ssm.conv"):
        pre, conv_pool = _conv(c, mp, a, plan, state["conv"], m_idx)
        cc = jax.nn.silu(pre)  # [T, d] f32
    with jax.named_scope("ssm.proj"):
        dbc = mm(cc.astype(x.dtype), mp["w_x"])
        if inner_norms:
            dtp = _rms32(dbc[:, :R], mp["dt_norm"], c.norm_eps).astype(x.dtype)
            Bm = _rms32(dbc[:, R:R + N], mp["b_norm"], c.norm_eps)
            Cm = _rms32(dbc[:, R + N:], mp["c_norm"], c.norm_eps)
        else:
            dtp = dbc[:, :R]
            Bm = dbc[:, R:R + N].astype(jnp.float32)
            Cm = dbc[:, R + N:].astype(jnp.float32)
        # the step size in float32 from the product on: it sits in an
        # exponent that compounds over the sequence (a bf16 result, 3
        # digits of a value near -5, would be a 2 % error of the step)
        dt = jax.nn.softplus(jnp.dot(
            dtp, mp["w_dt"], preferred_element_type=jnp.float32) + mp["b_dt"])
        A = -jnp.exp(mp["A_log"].astype(jnp.float32))  # [N, d]
    with jax.named_scope("ssm.kernel"):
        kernel = impl == "pallas"
        if plan.rows:
            op = ssm_ops.ssm_update if kernel else ssm_ops.ssm_update_jnp
            y, S = op(state["S"], m_idx, plan.tok_slot, plan.live, plan.fresh,
                      cc, dt, Bm, Cm, A)
        else:
            op = ssm_ops.ssm_scan if kernel else ssm_ops.ssm_scan_jnp
            y, S = op(state["S"], m_idx, plan.tok_slot, plan.flags,
                      cc, dt, Bm, Cm, A)
    with jax.named_scope("ssm.proj"):
        m = y + mp["D"] * cc
        y = m * jax.nn.silu(z.astype(jnp.float32))
        out = mm(y.astype(x.dtype), mp["w_out"])
    state = {"S": S, "conv": conv_pool}
    return (out, state, m) if scan_out else (out, state)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S] (padding = -1)
    k_pool: jax.Array,  # [La, NP, PS, Hk, Dh]
    v_pool: jax.Array,
    page_table: jax.Array,
    kv_lens: jax.Array,
    last_index=None,
    attn_impl: str = "jnp",
    mesh=None,
    ragged=None,  # as models/llama.forward's
    state: Optional[Dict[str, jax.Array]] = None,  # make_state_pool's
    slots: Optional[jax.Array] = None,  # int32 [B]: each row's slot; on the
    #   ragged step [3, SEG]: each segment's slot, first flat token, tokens
    #   (0: a dead entry). None: the scratch slot.
):
    """models/llama.forward for a hybrid model: the same operands and
    (logits, k_pool, v_pool), then the state pool. One token a row (the
    decode step), one prefill chunk [1, S], or the ragged flat step."""
    c = config
    B, S = tokens.shape
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        raise NotImplementedError(
            "a state-space model is not sharded over a model axis yet")
    if state is None:
        raise ValueError("a state-space model's forward needs its state pool")
    if ragged is not None and B != 1:
        raise ValueError("ragged forward takes a single flat [1, T] row")
    if slots is None:
        slots = jnp.zeros((3, 1) if ragged is not None else (B,), jnp.int32)
        if ragged is not None:  # one segment over the whole axis
            slots = slots.at[2, 0].set(S)
    plan = _plan(positions, slots, ragged is not None)
    hd = c.head_dim
    G = c.n_heads // c.n_kv_heads
    T = B * S

    h = embed_lookup(params["embed"], tokens)
    safe_pos = jnp.maximum(positions, 0)
    q_start = safe_pos[:, 0]
    q_len = jnp.sum((positions >= 0).astype(jnp.int32), axis=1)

    walk = None
    if attn_impl == "pallas" and (S == 1 or ragged is not None):
        if ragged is not None:
            from dynamo_tpu.ops.ragged_paged_attention import ragged_walk

            seg_pt, seg_kvl, rmeta = ragged
            walk = ragged_walk((c.n_kv_heads, G), k_pool, v_pool, seg_pt,
                               seg_kvl, rmeta, None, S)
        else:
            from dynamo_tpu.ops.paged_attention import decode_walk

            walk = decode_walk((c.n_kv_heads, G), k_pool, v_pool, page_table,
                               kv_lens, None, False)

    def mlp(h, lp):
        with jax.named_scope("ffn"):
            x = rms_norm(h, lp["mlp_norm"], c.norm_eps)
            gate = jax.nn.silu(mm(x, lp["w_gate"]))
            return h + mm(gate * mm(x, lp["w_up"]), lp["w_down"])

    def attention(h, lp, ap, rank, k_pool, v_pool):
        l_idx = jnp.asarray(rank, jnp.int32)
        with jax.named_scope("attn.proj"):
            x = rms_norm(h, lp["attn_norm"], c.norm_eps)
            q = mm(x, ap["wq"]).reshape(B, S, c.n_heads, hd)
            k = mm(x, ap["wk"]).reshape(B, S, c.n_kv_heads, hd)
            v = mm(x, ap["wv"]).reshape(B, S, c.n_kv_heads, hd)
        if ragged is not None:
            k_pool = _write_kv(k_pool, l_idx, k.reshape(S, 1, c.n_kv_heads, hd),
                               page_table, positions.reshape(S, 1))
            v_pool = _write_kv(v_pool, l_idx, v.reshape(S, 1, c.n_kv_heads, hd),
                               page_table, positions.reshape(S, 1))
        else:
            k_pool = _write_kv(k_pool, l_idx, k, page_table, positions)
            v_pool = _write_kv(v_pool, l_idx, v, page_table, positions)

        def kv_slab():
            return (jax.tree.map(lambda a: a[rank], k_pool),
                    jax.tree.map(lambda a: a[rank], v_pool))

        with jax.named_scope("attn.kernel"):
            qg = q.reshape(B, S, c.n_kv_heads, G, hd)
            if ragged is not None and attn_impl == "pallas":
                from dynamo_tpu.ops.ragged_paged_attention import ragged_paged_attention

                seg_pt, seg_kvl, rmeta = ragged
                attn = ragged_paged_attention(
                    qg[0], k_pool, v_pool, seg_pt, seg_kvl, rmeta, None,
                    l_idx, walk)[None]
            elif ragged is not None:
                attn = paged_attention_jnp(
                    qg[0][:, None], *kv_slab(), page_table,
                    safe_pos.reshape(S, 1), kv_lens)[:, 0][None]
            elif attn_impl == "pallas" and S == 1:
                from dynamo_tpu.ops.paged_attention import decode_paged_attention

                attn = decode_paged_attention(
                    qg[:, 0], k_pool, v_pool, page_table, kv_lens, None,
                    l_idx, walk)[:, None]
            elif attn_impl == "pallas":
                from dynamo_tpu.ops.flash_prefill import prefill_paged_attention

                attn = prefill_paged_attention(
                    qg, k_pool, v_pool, page_table, q_start, q_len, kv_lens,
                    None, l_idx)
            else:
                attn = paged_attention_jnp(qg, *kv_slab(), page_table,
                                           safe_pos, kv_lens)
        with jax.named_scope("attn.proj"):
            h = h + mm(attn.reshape(B, S, c.n_heads * hd), ap["wo"])
        return h, k_pool, v_pool

    def mamba_run(h, state, l0: int, m0: int, count: int):
        """`count` consecutive Mamba layers from layer l0 (Mamba layer m0):
        one scan over their indices, the stacks read in place."""
        def body(carry, i):
            h, state = carry
            lp = jax.tree.map(lambda a: a[l0 + i], params["layers"])
            mp = jax.tree.map(lambda a: a[m0 + i], params["mamba"])
            x = rms_norm(h, lp["attn_norm"], c.norm_eps).reshape(T, c.dim)
            out, state = _mamba_mixer(c, mp, x, plan, state, m0 + i, attn_impl)
            return (mlp(h + out.reshape(B, S, c.dim), lp), state), None

        (h, state), _ = lax.scan(body, (h, state),
                                 jnp.arange(count, dtype=jnp.int32))
        return h, state

    def attention_layer(h, k_pool, v_pool, at, rank):
        lp = jax.tree.map(lambda a: a[at], params["layers"])
        ap = jax.tree.map(lambda a: a[rank], params["attn"])
        h, k_pool, v_pool = attention(h, lp, ap, rank, k_pool, v_pool)
        return mlp(h, lp), k_pool, v_pool

    # whole periods (`offset` Mamba layers, the attention layer, the rest
    # of the period's Mamba layers) are one scan over the period's index:
    # two Mamba bodies and one attention layer compiled however many
    # periods there are
    P, O = c.attn_layer_period, c.attn_layer_offset
    n_periods = c.n_layers // P

    def period(carry, p):
        h, k_pool, v_pool, state = carry
        if O:
            h, state = mamba_run(h, state, p * P, p * (P - 1), O)
        h, k_pool, v_pool = attention_layer(h, k_pool, v_pool, p * P + O, p)
        if P - 1 - O:
            h, state = mamba_run(h, state, p * P + O + 1, p * (P - 1) + O, P - 1 - O)
        return (h, k_pool, v_pool, state), None

    if n_periods:
        (h, k_pool, v_pool, state), _ = lax.scan(
            period, (h, k_pool, v_pool, state),
            jnp.arange(n_periods, dtype=jnp.int32))
    # what is left of a model whose depth is no multiple of the period
    l, m = n_periods * P, n_periods * (P - 1)
    for rank, at in enumerate(c.attn_layers + (c.n_layers,)):
        if at < l:
            continue
        if at > l:
            h, state = mamba_run(h, state, l, m, at - l)
            m += at - l
        if at == c.n_layers:
            break
        h, k_pool, v_pool = attention_layer(h, k_pool, v_pool, at, rank)
        l = at + 1

    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["norm_f"], c.norm_eps)
        if last_index is not None:
            if getattr(last_index, "ndim", 0) >= 1:
                idx = last_index.reshape((1, -1, 1) if ragged is not None
                                         else (-1, 1, 1))
                h = jnp.take_along_axis(h, idx, axis=1)
            else:
                h = lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
        logits = tied_logits(h, params["embed"]).astype(jnp.float32)
    return logits, k_pool, v_pool, state


# --------------------------------------------------------------------------
# what the runner asks of a model with a cache beside its pages
# --------------------------------------------------------------------------

def _side_make_pool(config: ModelConfig, units: int, page_size: int, dtype):
    # `S` is float32, as the published model keeps it; the convolution's
    # inputs are the activations' dtype
    return make_state_pool(config, units, conv_dtype=dtype)


def _side_unit_bytes(config: ModelConfig, page_size: int, dtype) -> int:
    return state_slot_bytes(config, conv_dtype=dtype)


def _side_rows(sides, B: int, max_pages: int) -> jax.Array:
    """int32 [B]: each row's state slot. Pad rows (None, and behind the
    last) name the scratch slot and, having no position, change none."""
    rows = np.zeros(B, np.int32)
    rows[: len(sides)] = [s or 0 for s in sides]
    return jnp.asarray(rows)


def _side_segs(sides, lens, seg_cap: int, t_bucket: int,
               max_pages: int) -> jax.Array:
    """int32 [3, seg_cap]: each segment's slot, first flat token and token
    count; dead entries hold no tokens."""
    seg = np.zeros((3, seg_cap), np.int32)
    n = len(lens)
    seg[0, :n] = [s or 0 for s in sides]
    seg[1, :n] = np.cumsum([0] + lens[:-1])
    seg[2, :n] = lens
    return jnp.asarray(seg)


SIDE = SideCacheOps("state", _side_make_pool, _side_unit_bytes, _side_rows,
                    _side_segs, forward)
