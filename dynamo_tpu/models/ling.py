"""Ling-3.0's hybrid decoder: Kimi-delta-attention (KDA) mixers with a gated
latent-attention (MLA) layer closing every `kda_layer_period` (layer l is MLA
iff (l + 1) % period == 0), `n_dense_layers` leading dense SwiGLU MLPs, then
grouped sigmoid experts with one shared expert (models/moe.py, as
deepseek-v3.2 routes), pre-norm residuals, untied head. The equations are
benchmark/reference/ling3_decoder.py's.

A KDA mixer, per token: q~, k~, v~ = silu(conv(x W)) (causal depthwise
convolutions of `kda_conv` taps); q = l2norm(q~) d_k^-1/2, k = l2norm(k~) a
head; the log-decay a channel g = lower * sigmoid(exp(A_log_h) (x W_a +
dt_bias)) in (lower, 0); beta = sigmoid(x W_beta) a head; the state a head
S [d_k, d_v] (float32) steps by ops/kda.py's delta rule and o = S^T q; out =
(rms_head(o) * sigmoid(x W_g)_h) W_o. No position term. The MLA layer is
models/mla.py's absorbed latent attention as it is (no query compression),
its heads under the same head-wise sigmoid gate before W_o.

What differs from models/llama.py, and why this is a forward of its own: the
layers are not alike. Runs of consecutive layers of one kind (KDA or MLA) and
one feed-forward (dense or routed) are each a scan over their indices; the
whole periods behind the leading dense layers are alike and are one scan over
the period's index with the run of KDA layers a scan inside it: five bodies
compiled at 18 layers (2 dense KDA, 3 routed KDA, MLA; then 5 routed KDA, MLA
a period), however many periods follow.

A sequence carries, besides the latent pages of its MLA layers (the KV pool
holds `config.kv_layers` of them, a layer's index its rank among them), one
**state slot** (engine/side_cache.StateSlots, the kind jamba's is): for every
KDA layer `S` [H, d_k, d_v] float32 and the last `kda_conv - 1` inputs of the
three convolutions. Slot 0 is scratch; a sequence's first token (position 0)
starts from zeros whatever its slot held. Step programs: one token a row (the
decode loop; `kda_update`) and one prefill chunk [1, S] (`kda_chunk`); a mixed
iteration is the two dispatches (Runner.fuses_mixed is False: latent
attention has no ragged program).

Parameter tree: embed [V, E], norm_f [E], lm_head [E, V]; kda.{wq, wk, wv, wa
[Lk, E, H d_k]; w_beta, w_g [Lk, E, H]; conv [Lk, K, 3 H d_k] (q, k, v side by
side); A_log [Lk, H]; dt_bias [Lk, H d_k]; o_norm [Lk, d_v]; wo [Lk, H d_v,
E]}; mla.{wq, wkv_a, kv_norm, wkv_b, w_g, wo} [La, ...]; layers_dense.{
attn_norm, mlp_norm, w_gate, w_up, w_down} (the leading layers) and layers.{
attn_norm, mlp_norm, w_router, router_bias, we_*, ws_*} (the rest), in model
order. Every drawn matrix is `[..., in, out]` (the convolution `[K, .]`:
fan-in K); A_log, dt_bias, router_bias and the norm weights are fills
(benchmark/serve.py draws what init_params draws from its key and keeps what
it fills).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.jamba import _side_rows  # a row's slot, as Jamba's
from dynamo_tpu.models.mla import _mla_attention
from dynamo_tpu.models.moe import EXPERT_STACKS, _moe_block, experts_kernel_stack
from dynamo_tpu.models.quant import embed_lookup, mm
from dynamo_tpu.models.toolkit import Params, SideCacheOps, rms_norm
from dynamo_tpu.ops import kda as kda_ops

# the fills of the decay gate: a channel's half-life, at a pre-activation of
# 0, spaced evenly in log over a head's channels from HALF_LIFE[0] to
# HALF_LIFE[1] tokens, and exp(A_log) spaced evenly over the heads in
# A_SCALE (the pre-activation's own spread, about one, moves a half-life by
# e^+-1). With A_log = dt_bias = 0 a drawn model would forget within a token
# (g about -2.5 a step) and a state lost between two chunks would change no
# logit: `correct` has to see the state carried (benchmark/configs/
# ling-3.0-flash-vl.json, `assumed.gate_fills`)
HALF_LIFE = (32.0, 512.0)
A_SCALE = (0.75, 1.25)


def gate_fills(c: ModelConfig):
    """(A_log [H], dt_bias [H d_k]) as numpy float32."""
    H, dk = c.n_heads, c.kda_head_dim
    a = np.linspace(*A_SCALE, H)
    share = np.log(2.0) / (-c.kda_gate_lower * np.geomspace(*HALF_LIFE, dk))
    at_rest = np.log(share / (1.0 - share))  # sigmoid^-1
    return (np.log(a).astype(np.float32),
            (at_rest[None, :] / a[:, None]).reshape(H * dk).astype(np.float32))


# --------------------------------------------------------------------------
# init + state pool
# --------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    c = config
    E, H, dk, K = c.dim, c.n_heads, c.kda_head_dim, c.kda_conv
    Lk, La = c.kda_layers, c.kv_layers
    nd, Lm = c.n_dense_layers, c.n_layers - c.n_dense_layers
    dn, dr, dv, dc = (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                      c.kv_lora_rank)
    k = jax.random.split(key, 32)

    def w(kk, fan_in, *shape):
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * (fan_in**-0.5)).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    a_log, dt_bias = gate_fills(c)
    params: Params = {
        "embed": w(k[0], E, c.vocab_size, E),
        "norm_f": ones(E),
        "lm_head": w(k[1], E, E, c.vocab_size),
        "kda": {
            "wq": w(k[2], E, Lk, E, H * dk),
            "wk": w(k[3], E, Lk, E, H * dk),
            "wv": w(k[4], E, Lk, E, H * dk),
            "wa": w(k[5], E, Lk, E, H * dk),
            "w_beta": w(k[6], E, Lk, E, H),
            "w_g": w(k[7], E, Lk, E, H),
            "conv": w(k[8], K, Lk, K, 3 * H * dk),
            "A_log": jnp.broadcast_to(jnp.asarray(a_log), (Lk, H)),
            "dt_bias": jnp.broadcast_to(jnp.asarray(dt_bias), (Lk, H * dk)),
            "o_norm": ones(Lk, dk),
            "wo": w(k[9], H * dk, Lk, H * dk, E),
        },
        "mla": {
            "wq": w(k[10], E, La, E, H * (dn + dr)),
            "wkv_a": w(k[11], E, La, E, dc + dr),
            "kv_norm": ones(La, dc),
            "wkv_b": w(k[12], dc, La, dc, H * (dn + dv)),
            "w_g": w(k[13], E, La, E, H),
            "wo": w(k[14], H * dv, La, H * dv, E),
        },
        "layers": {
            "attn_norm": ones(Lm, E),
            "mlp_norm": ones(Lm, E),
            "w_router": w(k[15], E, Lm, E, c.n_experts),
            "we_gate": w(k[16], E, Lm, c.experts_held, E, c.moe_ffn_dim),
            "we_up": w(k[17], E, Lm, c.experts_held, E, c.moe_ffn_dim),
            "we_down": w(k[18], c.moe_ffn_dim, Lm, c.experts_held, c.moe_ffn_dim, E),
        },
    }
    if c.moe_router_bias:
        params["layers"]["router_bias"] = jnp.zeros((Lm, c.n_experts), jnp.float32)
    if c.n_shared_experts:
        F = c.shared_ffn_dim
        params["layers"].update(
            ws_gate=w(k[19], E, Lm, E, F), ws_up=w(k[20], E, Lm, E, F),
            ws_down=w(k[21], F, Lm, F, E))
    if nd:
        params["layers_dense"] = {
            "attn_norm": ones(nd, E),
            "mlp_norm": ones(nd, E),
            "w_gate": w(k[22], E, nd, E, c.ffn_dim),
            "w_up": w(k[23], E, nd, E, c.ffn_dim),
            "w_down": w(k[24], c.ffn_dim, nd, c.ffn_dim, E),
        }
    return params


def state_slot_bytes(config: ModelConfig, conv_dtype=jnp.bfloat16) -> int:
    """Bytes of one sequence's state over all KDA layers (`S` in float32)."""
    c = config
    H, dk = c.n_heads, c.kda_head_dim
    return c.kda_layers * (
        H * dk * dk * 4
        + (c.kda_conv - 1) * 3 * H * dk * jnp.dtype(conv_dtype).itemsize)


def make_state_pool(config: ModelConfig, slots: int, state_dtype=jnp.float32,
                    conv_dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """{"S": [Lk, slots, H, d_k, d_v], "conv": [Lk, slots, K - 1, 3 H d_k]}:
    zeros."""
    c = config
    H, dk = c.n_heads, c.kda_head_dim
    return {
        "S": jnp.zeros((c.kda_layers, slots, H, dk, dk), state_dtype),
        "conv": jnp.zeros((c.kda_layers, slots, c.kda_conv - 1, 3 * H * dk),
                          conv_dtype),
    }


# --------------------------------------------------------------------------
# the KDA mixer
# --------------------------------------------------------------------------


class _Plan(NamedTuple):
    """A forward's state bookkeeping, read by every KDA layer. `rows`: one
    token a row (the decode step), else one segment on the token axis."""
    rows: bool
    live: jax.Array  # [T] bool: a real token
    fresh: jax.Array  # rows: [T] bool, the row starts its sequence; a
    #   segment: scalar bool
    slot: jax.Array  # rows: [T] int32; a segment: scalar int32
    n_live: Optional[jax.Array] = None  # a segment's real tokens (they lead)


def _plan(positions: jax.Array, slots: jax.Array) -> _Plan:
    B, S = positions.shape
    if S == 1:
        pos = positions[:, 0]
        return _Plan(True, pos >= 0, pos == 0, slots.astype(jnp.int32))
    if B != 1:
        raise NotImplementedError(
            "a model with KDA layers takes prefill chunks one at a time, "
            "never as a padded [N, S] pack")
    pos = positions[0]
    live = pos >= 0
    return _Plan(False, live, pos[0] == 0, slots.astype(jnp.int32)[0],
                 jnp.sum(live.astype(jnp.int32)))


def _conv(c: ModelConfig, w, a, plan: _Plan, conv_pool, m_idx):
    """The causal depthwise convolutions of q, k and v side by side over each
    sequence's inputs, the first K - 1 reaching into the slot's stored ones.
    a [T, 3 H d_k]. Returns (silu of it [T, .] f32, conv_pool with every live
    row's, or the segment's, last K - 1 inputs stored)."""
    K = c.kda_conv
    n_slots = conv_pool.shape[1]
    w = w.astype(jnp.float32)  # [K, .]
    if plan.rows:
        prev = conv_pool[m_idx, plan.slot]  # [T, K-1, .]
        prev = jnp.where(plan.fresh[:, None, None], 0, prev)
        window = jnp.concatenate([prev, a[:, None].astype(prev.dtype)], axis=1)
        out = jnp.einsum("tkd,kd->td", window.astype(jnp.float32), w)
        dst = jnp.where(plan.live, plan.slot, n_slots)
        return jax.nn.silu(out), conv_pool.at[m_idx, dst].set(
            window[:, 1:], mode="drop")
    T = a.shape[0]
    prev = jnp.where(plan.fresh, 0, conv_pool[m_idx, plan.slot])  # [K-1, .]
    full = jnp.concatenate([prev, a.astype(prev.dtype)], axis=0)  # [K-1+T, .]
    out = sum(w[j] * full[j:j + T].astype(jnp.float32) for j in range(K))
    # the last K - 1 inputs behind the segment's real tokens (full[n + j] is
    # the input of token n - (K - 1) + j; a short segment keeps old ones)
    last = lax.dynamic_slice_in_dim(full, plan.n_live, K - 1, axis=0)
    return jax.nn.silu(out), conv_pool.at[m_idx, plan.slot].set(last)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_mixer(c: ModelConfig, kp, x, plan: _Plan, state, m_idx, impl: str):
    """x [T, E] (normed) -> ([T, E], state)."""
    H, dk = c.n_heads, c.kda_head_dim
    T = x.shape[0]
    with jax.named_scope("kda.proj"):
        a = jnp.concatenate([mm(x, kp["wq"]), mm(x, kp["wk"]), mm(x, kp["wv"])],
                            axis=-1)
        # the decay in float32 from the product on: it sits in an exponent
        # that compounds over the sequence
        pre = jnp.dot(x, kp["wa"], preferred_element_type=jnp.float32)
        scale = jnp.repeat(jnp.exp(kp["A_log"].astype(jnp.float32)), dk)
        g = c.kda_gate_lower * jax.nn.sigmoid(scale * (pre + kp["dt_bias"]))
        beta = jax.nn.sigmoid(mm(x, kp["w_beta"]).astype(jnp.float32))  # [T, H]
        gate = jax.nn.sigmoid(mm(x, kp["w_g"]).astype(jnp.float32))
    with jax.named_scope("kda.conv"):
        qkv, conv_pool = _conv(c, kp["conv"], a, plan, state["conv"], m_idx)
        q, k, v = (qkv[:, i * H * dk:(i + 1) * H * dk].reshape(T, H, dk)
                   for i in range(3))
        q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
        # a padding token is the identity of the recurrence
        g = jnp.where(plan.live[:, None, None], g.reshape(T, H, dk), 0.0)
        beta = jnp.where(plan.live[:, None], beta, 0.0)
    with jax.named_scope("kda.kernel"):
        kernel = impl == "pallas"
        if plan.rows:
            op = kda_ops.kda_update if kernel else kda_ops.kda_update_jnp
            o, S = op(state["S"], m_idx, plan.slot, plan.live, plan.fresh,
                      q, k, v, g, beta)
        else:
            op = kda_ops.kda_chunk if kernel else kda_ops.kda_chunk_jnp
            S0 = jnp.where(plan.fresh, 0.0,
                           state["S"][m_idx, plan.slot].astype(jnp.float32))
            o, S1 = op(S0, q, k, v, g, beta)
            S = state["S"].at[m_idx, plan.slot].set(S1.astype(state["S"].dtype))
    with jax.named_scope("kda.proj"):
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c.norm_eps)
        o = o * kp["o_norm"] * gate[:, :, None]
        out = mm(o.reshape(T, H * dk).astype(x.dtype), kp["wo"])
    return out, {"S": S, "conv": conv_pool}


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def forward(
    config: ModelConfig,
    params: Params,
    tokens: jax.Array,  # [B, S]
    positions: jax.Array,  # [B, S] (padding = -1)
    k_pool: jax.Array,  # [La, NP, PS, 1, the latent pool's width]
    v_pool: jax.Array,  # latent attention's 1-wide stub
    page_table: jax.Array,
    kv_lens: jax.Array,
    last_index=None,
    attn_impl: str = "jnp",
    mesh=None,
    ragged=None,
    state: Optional[Dict[str, jax.Array]] = None,  # make_state_pool's
    slots: Optional[jax.Array] = None,  # int32 [B]: each row's slot (a
    #   chunk's: [1]). None: the scratch slot.
):
    """models/llama.forward for Ling-3.0: the same operands, then
    (logits, k_pool, v_pool, picks [L_moe, B, S, k], listed [L_moe], the
    state pool). One token a row (the decode step) or one prefill chunk
    [1, S]."""
    c = config
    B, S = tokens.shape
    if mesh is not None and any(n > 1 for n in mesh.shape.values()):
        raise NotImplementedError("a model with KDA layers is not sharded yet")
    if ragged is not None:
        raise NotImplementedError(
            "latent attention has no ragged program: a model with KDA layers "
            "runs a mixed iteration as two dispatches")
    if state is None:
        raise ValueError("a model with KDA layers needs its state pool")
    if slots is None:
        slots = jnp.zeros((B,), jnp.int32)
    plan = _plan(positions, slots)
    E, H, dv = c.dim, c.n_heads, c.v_head_dim
    T = B * S
    real_rows = positions >= 0

    h = embed_lookup(params["embed"], tokens)
    safe_pos = jnp.maximum(positions, 0)
    q_start = safe_pos[:, 0]
    q_len = jnp.sum(real_rows.astype(jnp.int32), axis=1)
    moe_stack = experts_kernel_stack(c, params["layers"], T, mesh, attn_impl)
    # the latent decode kernel's list of live pages hangs on the lengths
    # alone: built here, once a step, for every MLA layer (models/llama.py)
    pages_walk = None
    if attn_impl == "pallas" and S == 1:
        from dynamo_tpu.ops.mla_attention import latent_walk

        pages_walk = latent_walk(H, k_pool, page_table, kv_lens)

    def ffn(h, lp, i_ffn, routed: bool):
        with jax.named_scope("ffn"):
            x = rms_norm(h, lp["mlp_norm"], c.norm_eps)
            if not routed:
                gate = jax.nn.silu(mm(x, lp["w_gate"]))
                return h + mm(gate * mm(x, lp["w_up"]), lp["w_down"]), None
            stack = None if moe_stack is None else moe_stack + (i_ffn,)
            out, sel, listed = _moe_block(c, lp, x, mesh, real_rows, stack)
            return h + out, (sel, listed)

    def one_layer(carry, l, rank, attn: bool, routed: bool):
        """Layer `l` (traced in a scan), `rank` its rank among the layers of
        its kind of mixer; `routed`: experts, not the dense MLP."""
        h, k_pool, v_pool, state = carry
        stack_name = "layers" if routed else "layers_dense"
        i_ffn = l - c.n_dense_layers if routed else l
        skip = EXPERT_STACKS if (routed and moe_stack is not None) else ()
        # (a weight under `quantize` is a dict of data and scales)
        lp = {n: jax.tree.map(lambda a: a[i_ffn], w)
              for n, w in params[stack_name].items() if n not in skip}
        mp = jax.tree.map(lambda a: a[rank], params["mla" if attn else "kda"])
        if attn:
            attn_out, k_pool, v_pool, _ = _mla_attention(
                c, {**mp, "attn_norm": lp["attn_norm"]}, h, k_pool,
                jnp.asarray(rank, jnp.int32), page_table, positions, safe_pos,
                kv_lens, attn_impl=attn_impl, q_start=q_start, q_len=q_len,
                ik_pool=v_pool, walk=pages_walk)
            with jax.named_scope("attn.proj"):
                x = rms_norm(h, lp["attn_norm"], c.norm_eps)
                gate = jax.nn.sigmoid(mm(x, mp["w_g"]).astype(jnp.float32))
                gated = attn_out.reshape(B, S, H, dv) * gate[..., None].astype(
                    attn_out.dtype)
                h = h + mm(gated.reshape(B, S, H * dv), mp["wo"])
        else:
            x = rms_norm(h, lp["attn_norm"], c.norm_eps).reshape(T, E)
            out, state = _kda_mixer(c, mp, x, plan, state, rank, attn_impl)
            h = h + out.reshape(B, S, E)
        h, out = ffn(h, lp, i_ffn, routed)
        return (h, k_pool, v_pool, state), out

    def run(carry, l0, r0, count: int, attn: bool, routed: bool):
        """`count` consecutive layers of one kind from layer l0 (rank r0)."""
        if count == 1:
            carry, out = one_layer(carry, l0, r0, attn, routed)
            return carry, None if out is None else jax.tree.map(
                lambda a: a[None], out)
        return lax.scan(
            lambda cr, i: one_layer(cr, l0 + i, r0 + i, attn, routed),
            carry, jnp.arange(count, dtype=jnp.int32))

    P, nd = c.kda_layer_period, c.n_dense_layers
    p0 = -(-nd // P)  # the first period with no dense layer
    n_periods = c.n_layers // P
    scanned = n_periods - p0 if n_periods - p0 >= 2 else 0
    head = p0 * P if scanned else c.n_layers  # layers walked run by run, in front

    def runs_of(layers):
        """[(attn, routed), first layer, count] of consecutive like layers."""
        runs = []
        for l in layers:
            key = (c.is_attn_layer(l), l >= nd)
            if runs and runs[-1][0] == key:
                runs[-1][2] += 1
            else:
                runs.append([key, l, 1])
        return runs

    rank_of = lambda l, attn: (l + 1) // P - 1 if attn else l - l // P
    picks, listed = [], []

    def walk(carry, runs):
        for (attn, routed), l0, count in runs:
            carry, out = run(carry, l0, rank_of(l0, attn), count, attn, routed)
            if out is not None:
                picks.append(out[0])
                listed.append(out[1])
        return carry

    carry = walk((h, k_pool, v_pool, state), runs_of(range(head)))
    if scanned:
        def period(carry, p):
            l0 = (p0 + p) * P
            carry, o_kda = run(carry, l0, rank_of(l0, False), P - 1, False, True)
            carry, o_mla = run(carry, l0 + P - 1, p0 + p, 1, True, True)
            return carry, jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), o_kda, o_mla)

        carry, out = lax.scan(period, carry,
                              jnp.arange(scanned, dtype=jnp.int32))
        picks.append(out[0].reshape((-1,) + out[0].shape[2:]))
        listed.append(out[1].reshape(-1))
        # what is left past the last whole period
        carry = walk(carry, runs_of(range(head + scanned * P, c.n_layers)))
    h, k_pool, v_pool, state = carry

    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["norm_f"], c.norm_eps)
        if last_index is not None:
            if getattr(last_index, "ndim", 0) >= 1:
                h = jnp.take_along_axis(h, last_index.reshape(-1, 1, 1), axis=1)
            else:
                h = lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
        logits = mm(h, params["lm_head"]).astype(jnp.float32)
    return (logits, k_pool, v_pool, jnp.concatenate(picks, axis=0),
            jnp.concatenate(listed, axis=0), state)


# --------------------------------------------------------------------------
# what the runner asks of a model with a cache beside its pages
# --------------------------------------------------------------------------

def _side_make_pool(config: ModelConfig, units: int, page_size: int, dtype):
    # `S` is float32; the convolutions' inputs are the activations' dtype
    return make_state_pool(config, units, conv_dtype=dtype)


def _side_unit_bytes(config: ModelConfig, page_size: int, dtype) -> int:
    return state_slot_bytes(config, conv_dtype=dtype)


def _side_segs(sides, lens, seg_cap: int, t_bucket: int, max_pages: int):
    raise NotImplementedError(
        "a model with KDA layers has no ragged step (Runner.fuses_mixed)")


SIDE = SideCacheOps("state", _side_make_pool, _side_unit_bytes, _side_rows,
                    _side_segs, forward)
