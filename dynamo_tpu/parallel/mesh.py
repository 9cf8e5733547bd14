"""Named device mesh + sharding policy.

Mesh axes (SURVEY.md §2.10 parallelism inventory):
  data   — DP / attention-DP replicas (router targets (worker, dp_rank))
  model  — tensor parallelism (megatron-style column/row splits)
  expert — MoE expert parallelism (all-to-all over ICI)
  seq    — sequence/context parallelism (ring attention)

On a v5e-64 slice a typical decode mesh is (data=2, model=8, expert=1,
seq=1) per 16-chip group; the policy below maps Llama-family params onto
(model) and the paged KV pool onto kv-heads×(model).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_EXPERT = "expert"
AXIS_SEQ = "seq"
AXIS_PIPE = "pipe"
ALL_AXES = (AXIS_DATA, AXIS_MODEL, AXIS_EXPERT, AXIS_SEQ, AXIS_PIPE)

# -- canonical layout tables (the dynshard contract surface) ---------------
# Every sharded op imports its PartitionSpecs from HERE instead of
# re-spelling the literals inline: dynlint's DYN-S rules treat these
# module-level declarations as the reviewed layout contract
# (docs/static_analysis.md), and the runtime layout guard
# (runtime/sanitizer.py) diffs live `jax.Array.sharding` against the
# policy built from the same table. Replication in particular must be
# spelled with a named constant — an inline `P()` on a large tensor is
# exactly the silent full-replication DYN-S003 exists to catch.

SPEC_REPLICATED = P()

# ring attention (ops/ring_attention.py): q [B, S, Hk, G, D],
# k/v [B, S, Hk, D], positions [B, S] — S sharded over the ring axis
SPEC_RING_Q = P(None, AXIS_SEQ, None, None, None)
SPEC_RING_KV = P(None, AXIS_SEQ, None, None)
SPEC_RING_POS = P(None, AXIS_SEQ)
# sequence-parallel activations [B, S, E] (models/llama.py ring path)
SPEC_SEQ_ACT = P(None, AXIS_SEQ, None)

# attention wrappers (ops/*_attention.py): flat-token / decode q
# [T|B, Hk, G, D] and prefill q [B, S, Hk, G, D] shard kv-heads on
# `model`, and so does the layer-stacked pool they read
SPEC_HEADS_TOK = P(None, AXIS_MODEL, None, None)
SPEC_HEADS_BATCH = P(None, None, AXIS_MODEL, None, None)
# one layer's paged KV [NP, PS, Hk, D] (the prefill→decode handoff)
SPEC_KV_PAGES = P(None, None, AXIS_MODEL, None)
# layer-stacked pools [L, NP, PS, Hk, D] + int8 scales [L, NP, PS, Hk]
# (the attention wrappers' operands; ops/block_copy.py exports)
SPEC_KV_POOL = P(None, None, None, AXIS_MODEL, None)
SPEC_KV_POOL_SCALES = P(None, None, None, AXIS_MODEL)
# MLA latent pool [L, NP, PS, 1, Dl]: Hk == 1 by construction (the cache is
# per-token latent, not per-head), so it CANNOT shard kv-heads and is
# small enough to replicate — deliberately, hence a named declaration
SPEC_MLA_LATENT_POOL = P(None, None, None, None, None)

# MoE dispatch (ops/moe_dispatch.py): tokens [T, E] over `expert`,
# expert weights [n_exp, E, F] EP-sharded (+F on `model` for EP x TP)
SPEC_MOE_TOKENS = P(AXIS_EXPERT, None)
SPEC_MOE_GATE_UP = P(AXIS_EXPERT, None, AXIS_MODEL)
SPEC_MOE_DOWN = P(AXIS_EXPERT, AXIS_MODEL, None)

# pipeline parallel (ops/pipeline_parallel.py): layer-stacked leaves and
# per-stage KV pools shard their leading [L] axis on `pipe`
SPEC_PIPE_STAGE = P(AXIS_PIPE)


def ring_specs(axis: str = AXIS_SEQ) -> Tuple[P, P, P]:
    """(q, kv, positions) ring-attention specs for a ring over `axis`."""
    if axis == AXIS_SEQ:
        return SPEC_RING_Q, SPEC_RING_KV, SPEC_RING_POS
    return (P(None, axis, None, None, None), P(None, axis, None, None),
            P(None, axis))


def attention_specs(axis: str = AXIS_MODEL) -> Tuple[P, P, P]:
    """(heads, kv_pool, kv_pool_scales) for flat-token/decode attention."""
    if axis == AXIS_MODEL:
        return SPEC_HEADS_TOK, SPEC_KV_POOL, SPEC_KV_POOL_SCALES
    return (P(None, axis, None, None), kv_pool_specs(axis),
            P(None, None, None, axis))


def prefill_attention_specs(axis: str = AXIS_MODEL) -> Tuple[P, P, P]:
    """(heads, kv_pool, kv_pool_scales) for batched [B, S, ...] prefill."""
    if axis == AXIS_MODEL:
        return SPEC_HEADS_BATCH, SPEC_KV_POOL, SPEC_KV_POOL_SCALES
    return (P(None, None, axis, None, None), kv_pool_specs(axis),
            P(None, None, None, axis))


def moe_specs(axis: str = AXIS_EXPERT,
              model_axis: Optional[str] = None) -> Tuple[P, P, P]:
    """(tokens, we_gate/we_up, we_down) EP dispatch specs."""
    if axis == AXIS_EXPERT and model_axis == AXIS_MODEL:
        return SPEC_MOE_TOKENS, SPEC_MOE_GATE_UP, SPEC_MOE_DOWN
    return (P(axis, None), P(axis, None, model_axis),
            P(axis, model_axis, None))


def pipe_specs(axis: str = AXIS_PIPE) -> P:
    """Leading-[L]-axis stage spec for pipeline-parallel leaves."""
    return SPEC_PIPE_STAGE if axis == AXIS_PIPE else P(axis)


def kv_pool_specs(axis: str = AXIS_MODEL) -> P:
    """Layer-stacked [L, NP, PS, Hk, D] pool spec (block_copy exports)."""
    return SPEC_KV_POOL if axis == AXIS_MODEL else P(None, None, None,
                                                     axis, None)


def reshard_kv_pages(kv_pages, mesh: Mesh,
                     spec: P = SPEC_KV_PAGES):
    """Declared reshard helper for the prefill→decode KV handoff
    (ROADMAP item 5 seam): moving KV state between role-specialized
    layouts MUST go through here so the layout change is an explicit,
    greppable declaration — DYN-S005 exempts tensors it carries."""
    return jax.device_put(kv_pages, NamedSharding(mesh, spec))


@dataclass(frozen=True)
class MeshConfig:
    data: int = 1
    model: int = 1
    expert: int = 1
    seq: int = 1
    # pipeline stages: layer-stacked params and the KV pool shard their
    # leading [L] axis; the GPipe schedule (ops/pipeline_parallel.py)
    # runs them stage-parallel. Trailing axis so pipe=1 configs keep
    # their device layout from earlier rounds.
    pipe: int = 1

    @property
    def n_devices(self) -> int:
        return self.data * self.model * self.expert * self.seq * self.pipe

    @property
    def shape(self) -> Tuple[int, int, int, int, int]:
        return (self.data, self.model, self.expert, self.seq, self.pipe)


def make_mesh(config: MeshConfig, devices: Optional[list] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if len(devices) < config.n_devices:
        raise ValueError(
            f"mesh {config.shape} needs {config.n_devices} devices, have {len(devices)}"
        )
    arr = np.asarray(devices[: config.n_devices]).reshape(config.shape)
    return Mesh(arr, ALL_AXES)


def single_device_mesh() -> Mesh:
    return make_mesh(MeshConfig())


@dataclass
class ShardingPolicy:
    """PartitionSpecs for a transformer served on the mesh.

    Column-parallel projections shard their output dim on `model`;
    row-parallel shard their input dim — XLA emits the single all-reduce per
    block (attention out-proj + MLP down-proj), the standard megatron split.
    The paged KV pool shards kv-heads on `model` so decode attention needs
    no cross-chip traffic for cache reads.
    """

    mesh: Mesh

    # -- params ------------------------------------------------------------
    def param_spec(self, path: str) -> P:
        """Spec by parameter name. Per-layer weights are stacked on a
        leading [n_layers] axis (models/llama.py), so layer params carry a
        leading None."""
        # embed quantizes per-ROW (scale [V, 1], reduced over E) unlike the
        # [..., in, out] weights, so its scale replicates instead of
        # following the generic collapsed-contraction rule below
        # pipeline stages own contiguous layer blocks: every layer-stacked
        # leaf shards its leading [L] axis on `pipe` (other dims stay
        # replicated — pipe>1 requires model==1, enforced by ModelRunner)
        if self.mesh.shape.get(AXIS_PIPE, 1) > 1 and path.startswith("layers/"):
            return P(AXIS_PIPE)
        if path.endswith("embed/s"):
            return P()
        # int8 weight-only quantization (models/quant.py): the q tensor
        # shards exactly like the base weight; the scale [.., 1, out]
        # shards only where the base sharded its LAST (output) dim
        if path.endswith(("/q", "/s")):
            base = self.param_spec(path[:-2])
            if path.endswith("/q"):
                return base
            # scale = base shape with the contraction dim (-2) collapsed to
            # 1: keep every base axis (incl. expert) except that dim, or
            # MoE scales replicate across EP ranks and waste the memory the
            # quantization saved
            if len(base) < 2:
                return base
            return P(*base[:-2], None, base[-1])
        # LoRA factors [L, n_slots, in, r] / [L, n_slots, r, out]: shard the
        # dim that matches the target's megatron split; the rank dim and the
        # tiny opposite factor stay replicated
        if path.endswith(("wo_a", "w_down_a")):
            return P(None, None, AXIS_MODEL, None)  # in sharded (row-parallel target)
        if path.endswith(("wq_b", "wk_b", "wv_b", "w_gate_b", "w_up_b")):
            return P(None, None, None, AXIS_MODEL)  # out sharded (column-parallel)
        if path.endswith(("_a", "_b")):
            return P()
        if path.endswith(("wq", "wk", "wv", "w_gate", "w_up", "ws_gate", "ws_up")):
            return P(None, None, AXIS_MODEL)  # [L, E, out] column parallel
        if path.endswith(("wo", "w_down", "ws_down")):
            return P(None, AXIS_MODEL, None)  # [L, in, E] row parallel
        if path.endswith(("bq", "bk", "bv")):
            return P(None, AXIS_MODEL)  # [L, out] follows the column split
        if path.endswith("embed"):
            return P(None, AXIS_MODEL)  # [V, E] shard E
        if path.endswith("lm_head"):
            return P(None, AXIS_MODEL)  # [E, V] shard V
        if path.endswith("w_router"):
            return P()  # [L, E, n_exp] MoE router replicated
        if path.endswith(("we_gate", "we_up")):
            return P(None, AXIS_EXPERT, None, AXIS_MODEL)  # [L, n_exp, E, F]
        if path.endswith("we_down"):
            return P(None, AXIS_EXPERT, AXIS_MODEL, None)  # [L, n_exp, F, E]
        return P()  # norms, scalars: replicated

    def params_sharding(self, params) -> dict:
        def _one(path_tuple, leaf):
            path = "/".join(str(getattr(k, "key", k)) for k in path_tuple)
            return NamedSharding(self.mesh, self.param_spec(path))

        return jax.tree_util.tree_map_with_path(_one, params)

    # -- kv cache ----------------------------------------------------------
    def kv_pool_spec(self) -> P:
        # token-major [layers, num_pages, page_size, kv_heads, head_dim];
        # pipeline stages hold their own layers' KV (pipe shards L)
        pipe = AXIS_PIPE if self.mesh.shape.get(AXIS_PIPE, 1) > 1 else None
        return P(pipe, None, None, AXIS_MODEL, None)

    def kv_pool_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.kv_pool_spec())

    def kv_pool_sharding_tree(self, pool):
        """Sharding for a pool that may be a plain array or an int8-KV
        dict {"q": [L,NP,PS,Hk,D], "s": [L,NP,PS,Hk]} — scales shard over
        the same kv-head axis as the data (axis 3 in both layouts).
        Pools whose head axis doesn't divide the model axis replicate
        instead: MLA latent pools have Hk=1 by construction (the cache is
        per-token, not per-head) and are small enough to replicate."""
        n_model = self.mesh.shape.get(AXIS_MODEL, 1)
        pipe = AXIS_PIPE if self.mesh.shape.get(AXIS_PIPE, 1) > 1 else None
        scale = NamedSharding(self.mesh, P(pipe, None, None, AXIS_MODEL))
        repl = NamedSharding(self.mesh, P())

        def _one(a):
            if a.shape[3] % n_model != 0:
                return repl
            return self.kv_pool_sharding() if a.ndim == 5 else scale

        return jax.tree.map(_one, pool)

    # -- activations -------------------------------------------------------
    def batch_spec(self) -> P:
        return P(AXIS_DATA)  # [B, ...] sharded over data axis

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec())
