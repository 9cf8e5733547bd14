"""Expert-parallel MoE with all-to-all token dispatch over the `expert`
mesh axis.

The wide-EP building block (reference deploys DeepSeek-class wide-EP via
engine backends + recipes, SURVEY.md §2.10; here it is native): tokens are
sharded across expert ranks; each rank routes its local tokens, packs them
into per-destination capacity buffers, exchanges them with one
`all_to_all` over ICI, runs its resident experts, and returns results with
a second all_to_all, combining with router weights.

Capacity model: each (src rank → dst rank) lane carries up to C tokens,
C = ceil(T_local * k / n_ranks * capacity_factor). Overflow tokens are
dropped (contribute zero), standard Switch/GShard semantics — with
capacity_factor ≥ n_experts/k the dispatch is lossless and matches the
dense reference exactly.

Engine integration note: models/moe.py takes this path on a mesh with an
expert axis (unquantized experts, token count divisible by the ranks);
everywhere else it computes every expert for every token.

Both paths hand the router's picks (`sel`) out beside the output: the
step programs return them (docs/observability.md, "Routed experts").
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.parallel.mesh import AXIS_EXPERT, SPEC_REPLICATED, moe_specs


def router_topk(logits: jax.Array, k: int, scoring: str = "softmax",
                norm_topk: bool = True, bias=None, routed_scale: float = 1.0,
                n_groups: int = 0, topk_groups: int = 0):
    """Top-k routing weights from f32 router logits. softmax = Mixtral/
    Qwen (softmax over the selected logits); sigmoid = DeepSeek-V3
    (independent gates, renormalized over the top-k). norm_topk=False
    (HF norm_topk_prob: false, Qwen2-MoE) keeps softmax-over-ALL-experts
    probabilities without renormalizing — the routed sum is deliberately
    < 1. One helper shared by every MoE path so dense, EP, and reference
    all route identically.

    `bias` [n_experts] is DeepSeek-V3's e_score_correction_bias
    (aux-loss-free load balancing): it shifts SELECTION only — the mixing
    weights come from the unbiased gates. `routed_scale` multiplies the
    final weights (HF routed_scaling_factor). `n_groups`/`topk_groups`
    enable V3's group-limited selection: keep the topk_groups expert
    groups whose top-2 member scores sum highest, ban the rest."""
    if scoring == "sigmoid":
        gates = jax.nn.sigmoid(logits)
        sel_scores = (
            gates + bias.astype(gates.dtype) if bias is not None else gates
        )
        if n_groups > 1 and 0 < topk_groups < n_groups:
            *lead, n_exp = sel_scores.shape
            per = n_exp // n_groups
            grouped = sel_scores.reshape(*lead, n_groups, per)
            top2, _ = lax.top_k(grouped, min(2, per))
            group_score = top2.sum(-1)  # [..., n_groups]
            _, keep_g = lax.top_k(group_score, topk_groups)
            keep = jnp.zeros(group_score.shape, bool)
            keep = jnp.put_along_axis(keep, keep_g, True, axis=-1,
                                      inplace=False)
            mask = jnp.repeat(keep, per, axis=-1)
            sel_scores = jnp.where(mask, sel_scores, -jnp.inf)
        if bias is not None or n_groups > 1:
            _, sel = lax.top_k(sel_scores, k)
            weights = jnp.take_along_axis(gates, sel, axis=-1)
        else:
            weights, sel = lax.top_k(gates, k)
        if norm_topk:
            weights = weights / jnp.maximum(
                jnp.sum(weights, axis=-1, keepdims=True), 1e-9
            )
    elif not norm_topk:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, sel = lax.top_k(probs, k)
    else:
        weights, sel = lax.top_k(logits, k)
        weights = jax.nn.softmax(weights, axis=-1)
    if routed_scale != 1.0:
        weights = weights * routed_scale
    return weights, sel


def _local_moe(x, w_router, we_gate, we_up, we_down, k: int, capacity: int, axis: str,
               model_axis=None, scoring: str = "softmax", norm_topk: bool = True,
               router_bias=None, routed_scale: float = 1.0,
               n_groups: int = 0, topk_groups: int = 0):
    """Per-shard body. x: [T, E] local tokens; we_*: [n_local, ...] resident
    experts; router weights replicated. Returns ([T, E], sel [T, k])."""
    n_ranks = lax.psum(1, axis)
    rank = lax.axis_index(axis)
    T, E = x.shape
    n_local = we_gate.shape[0]
    n_experts = n_local * n_ranks

    with jax.named_scope("moe.route"):
        logits = (x @ w_router).astype(jnp.float32)  # [T, n_experts]
        weights, sel = router_topk(
            logits, k, scoring, norm_topk, bias=router_bias,
            routed_scale=routed_scale, n_groups=n_groups,
            topk_groups=topk_groups)
        weights = weights.astype(x.dtype)

    # flatten (token, choice) pairs and bucket by destination rank
    flat_sel = sel.reshape(-1)  # [T*k] expert ids
    flat_tok = jnp.repeat(jnp.arange(T), k)  # [T*k]
    flat_w = weights.reshape(-1)
    dest = flat_sel // n_local  # destination rank per pair

    # position of each pair within its (dest rank, capacity) lane: running
    # count of earlier pairs with the same destination
    onehot = jax.nn.one_hot(dest, n_ranks, dtype=jnp.int32)  # [T*k, R]
    pos_in_dest = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
    keep = pos_in_dest < capacity

    # dispatch buffers [R, C, E] + bookkeeping [R, C]
    disp_x = jnp.zeros((n_ranks, capacity, E), x.dtype)
    disp_expert = jnp.zeros((n_ranks, capacity), jnp.int32)
    slot_r = jnp.where(keep, dest, n_ranks)  # OOB drop
    slot_c = jnp.where(keep, pos_in_dest, capacity)
    disp_x = disp_x.at[slot_r, slot_c].set(x[flat_tok], mode="drop")
    disp_expert = disp_expert.at[slot_r, slot_c].set(flat_sel % n_local, mode="drop")

    # exchange: [R, C, E] → every rank receives its inbound tokens
    with jax.named_scope("moe.dispatch"):
        recv_x = lax.all_to_all(disp_x, axis, split_axis=0, concat_axis=0, tiled=False)
        recv_expert = lax.all_to_all(disp_expert, axis, split_axis=0, concat_axis=0, tiled=False)
    # recv_x: [R, C, E] — row r = tokens sent by rank r to us

    rx = recv_x.reshape(n_ranks * capacity, E)
    re_ = recv_expert.reshape(n_ranks * capacity)

    # run resident experts on every received token, select by expert id.
    # With a model axis, each expert's F dim is TP-sharded: the down-proj
    # produces partial sums that one psum over `model` completes (the
    # megatron row-parallel pattern inside the EP shard)
    def expert_fn(wg, wu, wd):
        return (jax.nn.silu(rx @ wg) * (rx @ wu)) @ wd  # [RC, E]

    with jax.named_scope("moe.experts"):
        all_out = jax.vmap(expert_fn)(we_gate, we_up, we_down)  # [n_local, RC, E]
        if model_axis is not None:
            all_out = lax.psum(all_out, model_axis)
        out_tok = jnp.take_along_axis(
            all_out.transpose(1, 0, 2), re_[:, None, None], axis=1
        )[:, 0]  # [RC, E]

    # send results back
    with jax.named_scope("moe.combine"):
        back = lax.all_to_all(
            out_tok.reshape(n_ranks, capacity, E), axis, split_axis=0, concat_axis=0
        )  # [R, C, E] — row r = results for pairs we sent to rank r

    # combine: scatter-add weighted results back to source tokens
    y = jnp.zeros((T, E), jnp.float32)
    gathered = back[slot_r.clip(0, n_ranks - 1), slot_c.clip(0, capacity - 1)]
    gathered = jnp.where(keep[:, None], gathered.astype(jnp.float32), 0.0)
    y = y.at[flat_tok].add(gathered * flat_w[:, None].astype(jnp.float32))
    return y.astype(x.dtype), sel.astype(jnp.int32)


def moe_ep(
    x: jax.Array,  # [T, E] tokens, sharded over `axis` on dim 0
    w_router: jax.Array,  # [E, n_experts] replicated
    we_gate: jax.Array,  # [n_experts, E, F] sharded over `axis` on dim 0
    we_up: jax.Array,
    we_down: jax.Array,  # [n_experts, F, E]
    mesh: Mesh,
    n_experts_active: int,
    capacity_factor: float = 2.0,
    axis: str = AXIS_EXPERT,
    model_axis=None,  # set to "model" for EP x TP expert weights
    scoring: str = "softmax",
    norm_topk: bool = True,
    router_bias=None,  # [n_experts] selection bias (DeepSeek-V3)
    routed_scale: float = 1.0,
    n_groups: int = 0,  # group-limited selection (DeepSeek-V3)
    topk_groups: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Token-dispatch EP MoE. Returns ([T, E], sel int32 [T, k]: each
    token's routed experts), both with x's token sharding."""
    n_ranks = mesh.shape[axis]
    T_local = x.shape[0] // n_ranks
    n_experts = we_gate.shape[0]
    capacity = int(np.ceil(T_local * n_experts_active / n_ranks * capacity_factor))

    ma = model_axis
    # router_bias rides as an explicit replicated input: a traced array
    # captured in the shard_map closure would be rejected under jit
    has_bias = router_bias is not None

    def body(x, w_router, we_gate, we_up, we_down, *rest):
        return _local_moe(
            x, w_router, we_gate, we_up, we_down, k=n_experts_active,
            capacity=capacity, axis=axis, model_axis=ma, scoring=scoring,
            norm_topk=norm_topk, router_bias=rest[0] if has_bias else None,
            routed_scale=routed_scale, n_groups=n_groups,
            topk_groups=topk_groups,
        )

    tok_spec, gate_up_spec, down_spec = moe_specs(axis, ma)
    in_specs = [
        tok_spec,
        SPEC_REPLICATED,  # w_router [E, n_exp]
        gate_up_spec,  # [n_exp, E, F]: F TP-sharded when ma set
        gate_up_spec,
        down_spec,  # [n_exp, F, E]
    ]
    args = [x, w_router, we_gate, we_up, we_down]
    if has_bias:
        in_specs.append(SPEC_REPLICATED)
        args.append(router_bias)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(tok_spec, tok_spec),  # sel rides out on the token axis
    )
    return fn(*args)


def moe_dense_reference(x, w_router, we_gate, we_up, we_down, k: int,
                        scoring: str = "softmax", norm_topk: bool = True):
    """Unsharded dense top-k MoE (same math as models/llama.py _moe_block)."""
    logits = (x @ w_router).astype(jnp.float32)
    weights, sel = router_topk(logits, k, scoring, norm_topk)
    weights = weights.astype(x.dtype)

    def expert_fn(wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    all_out = jax.vmap(expert_fn)(we_gate, we_up, we_down)  # [n_exp, T, E]
    sel_out = jnp.take_along_axis(
        all_out.transpose(1, 0, 2), sel[..., None], axis=1
    )  # [T, k, E]
    return jnp.sum(sel_out * weights[..., None], axis=1)
