"""Mixture-of-experts block: token-choice top-k routing. On a mesh with
an expert axis tokens go to their experts by the wide-EP all-to-all
dispatch (ops/moe_dispatch.py). Off one, a forward of few rows on the chip
(a decode step) computes the routed experts with one Pallas kernel over a
work list of the experts its real rows picked, reading the stacked
weights in place (ops/moe_experts.py; `experts_kernel_stack` says when):
the step reads the weights of the experts it hit and no others. Every
other forward (a prefill chunk, int8 experts, a TP mesh, the CPU)
computes every expert for every token and picks the routed ones out
afterwards: n_experts / n_experts_active times the routed FLOPs and every
expert's weights read (128/6 = 21x at 128 experts, six a token); a chunk
hits nearly every expert, so only a sorted grouping would help it, which
is what is left of ROADMAP S4. Shared experts (DeepSeek / Qwen2-MoE) stay
out of the dispatch entirely.

A chip that holds a share of the experts (`ModelConfig.n_experts_held`
from `expert_first` on: one chip of an expert-parallel deployment, run
without the others) routes over all `n_experts`, weighs the picks as the
whole layer does, and computes the part of the layer its own experts
give plus the shared experts. What the absent experts would add is left
out and nothing stands in for them or their exchange; the picks stay ids
over the router's full width. Both paths above work on the HELD experts
alone.

The block hands the router's picks out beside its output, and
`routing_stats` reduces a forward's picks to the expert-load counters of
an engine iteration (docs/observability.md, "Routed experts").
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import is_quantized, mm


EXPERT_STACKS = ("we_gate", "we_up", "we_down")


def experts_kernel_stack(c: ModelConfig, layers, rows: int, mesh,
                         attn_impl: str):
    """The layer-stacked expert weights (we_gate, we_up, we_down) if a
    forward of `rows` rows computes its routed experts with the work-list
    kernel (ops/moe_experts.py), else None: the dense path. Decided from
    what the forward can see, all of it static: the Pallas kernels are in
    use (`attn_impl`, the runner's rule: Pallas on the chip, jnp
    elsewhere), no `expert` or `model` mesh axis above 1, unquantized
    experts, at most `moe_experts.row_bound` rows (32, or 64 under a wide
    router whose picks leave experts unread there: where every listed
    expert taking all rows is still bound by the weights it streams: a
    decode step and its chained steps, not a prefill chunk), and an ffn
    tile that fits VMEM. The caller takes the three stacks out of what
    its layer scan slices and hands `_moe_block` these and the layer."""
    from dynamo_tpu.ops import moe_experts

    we_gate = layers.get("we_gate")
    if (attn_impl != "pallas" or we_gate is None or is_quantized(we_gate)
            or rows > moe_experts.row_bound(c.n_experts, c.n_experts_active)):
        return None
    if mesh is not None and any(
            mesh.shape.get(a, 1) > 1 for a in ("expert", "model")):
        return None
    if moe_experts.ffn_tile(c.dim, c.moe_ffn_dim,
                            we_gate.dtype.itemsize) is None:
        return None
    return tuple(layers[k] for k in EXPERT_STACKS)


def _moe_block(c: ModelConfig, lp, x: jax.Array, mesh=None, valid=None,
               stack=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token-choice top-k MoE. With an expert mesh axis (and unquantized
    experts), tokens dispatch to their experts with one all_to_all over ICI
    and return with a second (ops/moe_dispatch.py — wide-EP); with `stack`
    (`experts_kernel_stack`'s three stacks and this layer's index into
    them: a decode step on the chip) the hit experts' work-list kernel
    reads the stacked weights in place; otherwise the dense path computes
    every expert under GSPMD expert sharding. `valid` bool [B, S] marks
    the real rows (None: all): a padding row lists no expert. x:
    [B, S, E] → ([B, S, E], sel int32 [B, S, k]: the experts the router
    picked for each token, the same on every path, listed int32 scalar:
    the entries of the work list the kernel walked, 0 off that path)."""
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
        return y.reshape(B, S, E) + shared, sel.reshape(B, S, -1), jnp.int32(0)
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

    if stack is not None:
        from dynamo_tpu.ops import moe_experts

        with jax.named_scope("moe.experts"):
            T = B * S
            rows = (jnp.ones((T,), bool) if valid is None
                    else valid.reshape(T))
            work, listed, wcol = moe_experts.hit_work_list(
                sel.reshape(T, -1), weights.reshape(T, -1), rows,
                c.expert_first, c.experts_held)
            routed = moe_experts.routed_experts(
                x.reshape(T, E), work, listed, wcol, *stack)
            routed = routed.astype(x.dtype).reshape(B, S, E)
        return routed + shared, sel, listed

    # every expert on every token, then the routed ones picked out: the
    # dense path's cost is n_experts / n_experts_active times the routed
    # FLOPs (what is left of ROADMAP S4); an expert mesh takes moe_ep above
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
    return routed + shared, sel, jnp.int32(0)


def routing_stats(sel: jax.Array, valid: jax.Array, c: ModelConfig,
                  listed=None) -> jax.Array:
    """One forward's expert load, reduced on the device. sel int32
    [L_moe, T, k] (any token layout flattened to T; ids over the router's
    full width), valid bool [T] (False = padding, not counted). The load
    is counted over the experts this chip HOLDS (`c.expert_first`,
    `c.experts_held`: all of them unless it holds a share), which are the
    ones whose weights a step here can read. `listed` int32 [L_moe] (None:
    the dense path ran) is what `_moe_block` says its work-list kernel
    walked in each expert layer. Returns f32 [5]:
      [0] routed token-slots: real tokens x k (one layer's; the same in all)
      [1] held experts selected at least once, summed over the expert layers
      [2] the share of the real tokens that picked a layer's fullest held
          expert (k / n_experts is even, 1.0 is one straggler), summed
          over the expert layers (0 in a forward with no real token)
      [3] token-slots that fell to held experts, summed over the expert
          layers ([0] x L_moe where every expert is held; a quarter of it
          where a quarter is and the routing is even)
      [4] work-list entries the expert kernels walked, summed over the
          expert layers: the kernel's own live count, not recomputed from
          `sel` ([1] where the kernel ran and padding rows listed nothing;
          0 where the dense path ran)
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
                      jnp.sum(load).astype(jnp.float32),
                      (jnp.float32(0) if listed is None
                       else jnp.sum(listed).astype(jnp.float32))])
