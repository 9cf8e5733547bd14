"""Mixture-of-experts block: token-choice top-k routing. On a mesh with
an expert axis tokens go to their experts by the wide-EP all-to-all
dispatch (ops/moe_dispatch.py). Everywhere else, a single chip included,
every expert is computed for every token and the routed ones are picked
out afterwards: n_experts / n_experts_active times the routed FLOPs and
every expert's weights read each step (128/6 = 21x at 128 experts, six a
token), which is ROADMAP S4's open item. Shared experts (DeepSeek /
Qwen2-MoE) stay out of the dispatch entirely.

A chip that holds a share of the experts (`ModelConfig.n_experts_held`
from `expert_first` on: one chip of an expert-parallel deployment, run
without the others) routes over all `n_experts`, weighs the picks as the
whole layer does, and computes the part of the layer its own experts
give plus the shared experts. What the absent experts would add is left
out and nothing stands in for them or their exchange; the picks stay ids
over the router's full width. It computes every HELD expert for every
token (S4 again, on a quarter of the weights).

The block hands the router's picks out beside its output, and
`routing_stats` reduces a forward's picks to the three expert-load
counters of an engine iteration (docs/observability.md, "Routed
experts").
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import mm


def _moe_block(c: ModelConfig, lp, x: jax.Array,
               mesh=None) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE. With an expert mesh axis (and unquantized
    experts), tokens dispatch to their experts with one all_to_all over ICI
    and return with a second (ops/moe_dispatch.py — wide-EP); otherwise the
    dense path computes every expert under GSPMD expert sharding. x:
    [B, S, E] → ([B, S, E], sel int32 [B, S, k]: the experts the router
    picked for each token, the same on both paths)."""
    from dynamo_tpu.models.quant import is_quantized

    B, S, E = x.shape
    # always-active shared experts (DeepSeek / Qwen2-MoE): a plain dense
    # FFN added to the routed output — never dispatched, so it stays out
    # of the EP all_to_all entirely
    shared = 0.0
    if c.n_shared_experts:
        with jax.named_scope("moe.shared"):
            gate = jax.nn.silu(mm(x, lp["ws_gate"]))
            shared = mm(gate * mm(x, lp["ws_up"]), lp["ws_down"])
            if "ws_gatectl" in lp:  # qwen2-moe: sigmoid-gated shared expert
                shared = shared * jax.nn.sigmoid(x @ lp["ws_gatectl"])
    ep = mesh is not None and mesh.shape.get("expert", 1) > 1
    if ep and c.holds_share:
        raise NotImplementedError(
            "a held share of the experts (n_experts_held) is one chip of an "
            "expert-parallel deployment; it does not combine with an expert "
            "mesh axis"
        )
    if ep and not is_quantized(lp["we_gate"]) and (B * S) % mesh.shape["expert"] == 0:
        from dynamo_tpu.ops.moe_dispatch import moe_ep

        model_axis = "model" if mesh.shape.get("model", 1) > 1 else None
        cf = c.moe_capacity_factor or (c.n_experts / c.n_experts_active)
        y, sel = moe_ep(
            x.reshape(B * S, E),
            lp["w_router"], lp["we_gate"], lp["we_up"], lp["we_down"],
            mesh, c.n_experts_active,
            capacity_factor=cf,
            model_axis=model_axis,
            scoring=c.moe_scoring,
            norm_topk=c.moe_norm_topk,
            router_bias=lp.get("router_bias"),
            routed_scale=c.moe_routed_scale,
            n_groups=c.n_expert_groups,
            topk_groups=c.topk_groups,
        )
        return y.reshape(B, S, E) + shared, sel.reshape(B, S, -1)
    from dynamo_tpu.ops.moe_dispatch import router_topk

    with jax.named_scope("moe.route"):
        router_logits = (x @ lp["w_router"]).astype(jnp.float32)  # [B,S,n_exp]
        weights, sel = router_topk(
            router_logits, c.n_experts_active, c.moe_scoring, c.moe_norm_topk,
            bias=lp.get("router_bias"), routed_scale=c.moe_routed_scale,
            n_groups=c.n_expert_groups, topk_groups=c.topk_groups,
        )
        weights = weights.astype(x.dtype)
        sel = sel.astype(jnp.int32)

    # every expert on every token, then the routed ones picked out: the
    # one-chip path's cost is n_experts / n_experts_active times the
    # routed FLOPs (ROADMAP S4); an expert mesh takes moe_ep above
    def one_expert(we_gate, we_up, we_down):
        gate = jax.nn.silu(mm(x, we_gate))
        return mm(gate * mm(x, we_up), we_down)  # [B,S,E]

    with jax.named_scope("moe.experts"):
        expert_out = jax.vmap(one_expert)(lp["we_gate"], lp["we_up"], lp["we_down"])
        # expert_out: [n_held, B, S, E]; select & mix
        local = sel
        if c.holds_share:
            # a pick of an expert held elsewhere keeps its place among the
            # k (the weights were renormalised over all of them) and adds
            # nothing here
            local = sel - c.expert_first
            here = (local >= 0) & (local < c.experts_held)
            weights = jnp.where(here, weights, 0)
            local = jnp.clip(local, 0, c.experts_held - 1)
        sel_out = jnp.take_along_axis(
            expert_out.transpose(1, 2, 0, 3),  # [B,S,n_held,E]
            local[..., None],
            axis=2,
        )  # [B,S,k,E]
        routed = jnp.sum(sel_out * weights[..., None], axis=2)
    return routed + shared, sel


def routing_stats(sel: jax.Array, valid: jax.Array, c: ModelConfig) -> jax.Array:
    """One forward's expert load, reduced on the device. sel int32
    [L_moe, T, k] (any token layout flattened to T; ids over the router's
    full width), valid bool [T] (False = padding, not counted). The load
    is counted over the experts this chip HOLDS (`c.expert_first`,
    `c.experts_held`: all of them unless it holds a share), which are the
    ones whose weights a step here can read. Returns f32 [4]:
      [0] routed token-slots: real tokens x k (one layer's; the same in all)
      [1] held experts selected at least once, summed over the expert layers
      [2] the share of the real tokens that picked a layer's fullest held
          expert (k / n_experts is even, 1.0 is one straggler), summed
          over the expert layers (0 in a forward with no real token)
      [3] token-slots that fell to held experts, summed over the expert
          layers ([0] x L_moe where every expert is held; a quarter of it
          where a quarter is and the routing is even)
    The caller sums them over an iteration's forwards and divides [1] and
    [2] by forwards x L_moe and [3] by L_moe (model_runner.MoeLoad)."""
    held = c.expert_first + jnp.arange(c.experts_held, dtype=sel.dtype)
    onehot = (sel[..., None] == held) & valid[None, :, None, None]
    load = jnp.sum(onehot, axis=(1, 2), dtype=jnp.int32)  # [L_moe, n_held]
    tokens = jnp.sum(valid, dtype=jnp.int32)
    hit = jnp.sum(load > 0, dtype=jnp.int32)
    share = jnp.sum(jnp.max(load, axis=-1) / jnp.maximum(tokens, 1))
    return jnp.stack([(tokens * sel.shape[-1]).astype(jnp.float32),
                      hit.astype(jnp.float32), share.astype(jnp.float32),
                      jnp.sum(load).astype(jnp.float32)])
