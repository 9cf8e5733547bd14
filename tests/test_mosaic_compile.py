"""Mosaic compiles of the decode attention kernel at real widths, for a
described (not attached) TPU v5e: what interpret mode cannot show — a
slice off the tiling, a scratch too large for VMEM, scalar-prefetch
operands too large for SMEM, a kernel that cannot be partitioned. Nothing
runs, so nothing here says anything about results or times.

One file on purpose: the process that describes the topology loads the
TPU library and holds it, so these live where one xdist worker gets them
all. The topology is described in a fixture, never at import.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.ops.paged_attention import (
    decode_paged_attention,
    decode_paged_attention_sharded,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


# name -> B, Hk, G, D, L, NP, PS, MP, windowed, int8 KV[, Dv, a sink]
_DECODE_SHAPES = {
    # the benchmark cell: MHA at D 96 (the by-rows routine), window live
    "phi3-b4": (4, 32, 1, 96, 32, 176, 64, 64, True, False),
    "phi3-b32": (32, 32, 1, 96, 32, 176, 64, 64, True, False),
    "mistral-b32": (32, 8, 4, 128, 32, 1408, 64, 64, False, False),
    # the worker's default shape: the work list (64 x 256 entries) beside
    # a 64 KB page table in SMEM
    "llama3.2-default-b64": (64, 8, 3, 128, 28, 2048, 16, 256, False, False),
    "gemma2-ps16": (8, 4, 2, 256, 26, 512, 16, 256, True, False),
    "mha-d128-ps16": (8, 32, 1, 128, 4, 256, 16, 128, False, False),
    "llama3.2-int8": (8, 8, 3, 128, 28, 256, 64, 64, True, True),
    "phi3-int8": (8, 32, 1, 96, 4, 176, 64, 64, True, True),
    # one KV head (ai21-jamba2-3b's cell: 20 query heads, 2 attention
    # layers, 2880 pages): the one-head routine on the 4-d view, 8 pages a
    # step; under a window; and four KV heads, one a shard on a 2x2
    "jamba2-mqa-b64": (64, 1, 20, 128, 2, 2880, 64, 64, False, False),
    "mqa-window-ps16": (8, 1, 8, 128, 4, 512, 16, 256, True, False),
    "mqa-a-shard-b64": (64, 4, 20, 128, 2, 2880, 64, 64, False, False),
    # mimo-v2-flash's two kinds of layer at its cell's pools under a page
    # table 96 wide: keys 256 wide beside values of 128, a head at a time
    # out of the token-major page; the window layers with their sink
    "mimo-global-b16": (16, 4, 16, 256, 2, 4096, 64, 96, False, False, 128, False),
    "mimo-window-b16": (16, 8, 8, 256, 9, 165, 64, 96, True, False, 128, True),
    "mimo-window-b32": (32, 8, 8, 256, 9, 165, 64, 96, True, False, 128, True),
    # head sizes that are no whole lane row, query heads that fill no
    # sublane tile
    "gqa-d64-g3": (8, 4, 3, 64, 4, 256, 16, 64, True, False),
    "gqa-d96-g2": (8, 4, 2, 96, 4, 256, 64, 64, False, False),
}


def _operands(shape, spec_of):
    """(q, k_pool, v_pool, page_table, kv_lens, window, layer, sink)."""
    B, Hk, G, D, L, NP, PS, MP, windowed, int8, *more = shape
    Dv, sinked = more or (D, False)

    def s(dims, dtype, kind):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=spec_of(kind))

    def pool(width):
        data = s((L, NP, PS, Hk, width), jnp.int8 if int8 else jnp.bfloat16, "pool")
        if int8:
            return {"q": data, "s": s((L, NP, PS, Hk), jnp.float32, "scales")}
        return data

    scalar = s((), jnp.int32, "rep")
    return (s((B, Hk, G, D), jnp.bfloat16, "heads"), pool(D), pool(Dv),
            s((B, MP), jnp.int32, "rep"), s((B,), jnp.int32, "rep"),
            scalar if windowed else None, scalar,
            s((Hk, G), jnp.float32, "sink") if sinked else None)


@pytest.mark.parametrize("name", list(_DECODE_SHAPES))
def test_decode_kernel_compiles_for_v5e(topo, name):
    one_chip = SingleDeviceSharding(topo.devices[0])
    *args, sink = _operands(_DECODE_SHAPES[name], lambda kind: one_chip)
    text = jax.jit(decode_paged_attention).lower(*args, sink=sink).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("name", ["phi3-b4", "mistral-b32", "llama3.2-int8",
                                  "mqa-a-shard-b64", "mimo-global-b16",
                                  "mimo-window-b16"])
def test_sharded_decode_kernel_compiles_for_v5e_2x2(topo, name):
    """Heads over four chips: each shard walks the same list on its own
    heads (mimo-v2-flash's global layers leave a shard one KV head, its
    window layers two), and no collective appears."""
    from dynamo_tpu.parallel.mesh import AXIS_MODEL, attention_specs

    mesh = Mesh(np.array(topo.devices).reshape(4), (AXIS_MODEL,))
    heads, pool, scales = attention_specs(AXIS_MODEL)
    specs = {"heads": heads, "pool": pool, "scales": scales, "rep": P(),
             "sink": P(AXIS_MODEL, None)}
    q, k, v, pt, kl, window, layer, sink = _operands(
        _DECODE_SHAPES[name], lambda kind: NamedSharding(mesh, specs[kind]))

    def fn(q, k, v, pt, kl, window, layer, sink):
        return decode_paged_attention_sharded(
            q, k, v, pt, kl, mesh, window=window, layer=layer, sink=sink)

    text = jax.jit(fn).lower(q, k, v, pt, kl, window, layer, sink).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-reduce(" not in text and "all-gather(" not in text


# -- the ragged (mixed-step) kernel at the geometries the benchmark's cells
# run it at. name -> T, Hk, G, D, L, NP, PS, MP, windowed, int8 KV, Dv, a sink
_RAGGED_SHAPES = {
    "phi3-t288": (288, 32, 1, 96, 32, 176, 64, 64, True, False, 96, False),
    "jamba2-t320": (320, 1, 20, 128, 2, 2880, 64, 64, False, False, 128, False),
    "mimo-global-t288": (288, 4, 16, 256, 2, 4096, 64, 96, False, False, 128, False),
    "mimo-window-t288": (288, 8, 8, 256, 9, 165, 64, 96, True, False, 128, True),
    # a two-chip shard of phi-3: G 1 under 32 KV heads takes the tile routine
    "phi3-a-shard-t288": (288, 16, 1, 96, 32, 176, 64, 64, True, False, 96, False),
    # the float32 body a head, which an int8 pool (and phi-3 whole) keeps
    "llama3.2-int8-t288": (288, 8, 3, 128, 28, 256, 64, 64, True, True, 128, False),
}
# what the kernel's body may hold, about 1.5 x what it does (139 equations at
# its longest, the window layers' call with a sink): every ragged T bucket's
# program of mimo2-agent-steady lowers it eleven times, and a first form of
# PR 45's select kernel at 17 k equations took that cell's set-up from 134 s
# to 356
_RAGGED_KERNEL_EQUATIONS = 210


def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _equations(inner)
    return n


@pytest.mark.parametrize("name", list(_RAGGED_SHAPES))
def test_ragged_kernel_compiles_for_v5e_and_reads_the_pools_in_place(topo, name):
    """One Mosaic call, its name the one a trace's readers look for, the
    pools read as the step programs carry them (no copy, slice or reshape
    that is no bitcast of a pool's shape, whole or a 4-d view), and the
    kernel's text within its bound."""
    import re

    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention, ragged_seg_cap, ragged_work_cap,
    )

    T, Hk, G, D, L, NP, PS, MP, windowed, int8, Dv, sinked = _RAGGED_SHAPES[name]
    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def pool(width):
        data = s((L, NP, PS, Hk, width), jnp.int8 if int8 else jnp.bfloat16)
        return {"q": data, "s": s((L, NP, PS, Hk), jnp.float32)} if int8 else data

    i32, SEG = jnp.int32, ragged_seg_cap(T)
    args = (s((T, Hk, G, D), jnp.bfloat16), pool(D), pool(Dv), s((SEG, MP), i32),
            s((SEG,), i32), s((5, ragged_work_cap(T)), i32),
            s((), i32) if windowed else None, s((), i32))
    kw = {"sink": s((Hk, G), jnp.float32)} if sinked else {}
    text = jax.jit(ragged_paged_attention).lower(*args, **kw).compile().as_text()
    calls = [l.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for l in text.splitlines() if "tpu_custom_call" in l and " = " in l]
    assert calls == ["ragged_paged_attention"]
    moved = re.compile(
        rf"= (bf16|s8)\[({L},)?{NP},{PS},({Hk},)?({D}|{Dv}|{Hk * D}|{Hk * Dv})\]\S* "
        r"(copy|reshape|dynamic-slice|slice)\(")
    assert [l.strip()[:160] for l in text.splitlines() if moved.search(l)] == []
    jaxpr = jax.make_jaxpr(lambda *a: ragged_paged_attention(*a, **kw))(*args)
    (call,) = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    assert _equations(call.params["jaxpr"]) <= _RAGGED_KERNEL_EQUATIONS


@pytest.mark.parametrize("name, tiles", [("llama3.2-t288", 8),
                                         ("mimo-window-t288", 8),
                                         ("phi3-t288", 4)])
def test_sharded_ragged_kernel_compiles_for_v5e_2x2(topo, name, tiles):
    """Heads over four chips. The walk is built outside `shard_map`, on
    pools that still have every head, FOR a shard's heads: a step brings the
    pages a shard's page of a quarter the bytes allows (Llama-3.2-3B's 8 KV
    heads: 8 where the whole pool's page would say 2; the widths of
    mimo-v2-flash's window layers, keys 256 beside values 128: 8 where 1;
    phi-3's 32 heads of one query each leave a shard 8, under the 32 from
    which G = 1 keeps the product a head: the tile routine, 4 pages a step),
    the list's length says so, and Mosaic takes the shard's kernel at that
    count; no collective appears."""
    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_paged_attention_sharded, ragged_seg_cap, ragged_work_cap,
    )
    from dynamo_tpu.parallel.mesh import AXIS_MODEL, attention_specs

    shapes = dict(_RAGGED_SHAPES, **{
        "llama3.2-t288": (288, 8, 3, 128, 28, 256, 64, 64, True, False, 128, False)})
    T, Hk, G, D, L, NP, PS, MP, windowed, _, Dv, _ = shapes[name]
    mesh = Mesh(np.array(topo.devices).reshape(4), (AXIS_MODEL,))
    heads, pool, _ = attention_specs(AXIS_MODEL)

    def s(dims, dtype, spec=P()):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    i32, SEG = jnp.int32, ragged_seg_cap(T)
    args = (s((T, Hk, G, D), jnp.bfloat16, heads),
            s((L, NP, PS, Hk, D), jnp.bfloat16, pool),
            s((L, NP, PS, Hk, Dv), jnp.bfloat16, pool), s((SEG, MP), i32),
            s((SEG,), i32), s((5, ragged_work_cap(T)), i32), s((), i32), s((), i32))

    def fn(q, k, v, pt, kl, meta, window, layer):
        return ragged_paged_attention_sharded(
            q, k, v, pt, kl, meta, mesh, window=window, layer=layer)

    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "all-reduce(" not in text and "all-gather(" not in text
    # the list the call walks: (work units) x (steps a unit can take)
    assert f"s32[{ragged_work_cap(T) * MP // tiles}]" in text


# -- latent attention at Mistral-Small-4's geometry (benchmark cell
# mistral4-chat-steady): 32 heads, latent rank 256 + a rotary key of 64, so
# the cached vector is 320 wide (2.5 lane tiles), 384 pages of 64 tokens under
# a page table 64 wide. name -> (kernel, rows or chunk length)
_MLA_SHAPES = {
    "decode-b1": ("decode", 1), "decode-b16": ("decode", 16),
    "decode-b32": ("decode", 32),  # the cell's widest bucket
    "prefill-s16": ("prefill", 16), "prefill-s256": ("prefill", 256),
    # the acc cap alone allowed a 128-row query block here (4096 rows x 32
    # heads), which Mosaic refuses: 19.7 MB of scoped VMEM against 16
    "prefill-s512": ("prefill", 512),
}


@pytest.mark.parametrize("name", list(_MLA_SHAPES))
def test_latent_attention_kernels_compile_for_v5e_at_rank_256(topo, name):
    from dynamo_tpu.ops.mla_attention import decode_mla_attention, prefill_mla_attention
    from dynamo_tpu.ops.paged_attention import decode_step

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    kind, n = _MLA_SHAPES[name]
    H, Dl, dc, NP, PS, MP = 32, 320, 256, 384, 64, 64
    kw = dict(dc=dc, scale=128 ** -0.5 * (0.1 * np.log(128) + 1) ** 2)
    pool = s((NP, PS, 1, Dl), jnp.bfloat16)
    if kind == "decode":
        # the walk's decision at these shapes: eight 41 KB pages a grid step
        assert decode_step((1, H), pool, None, MP, False) == ("by_tiles", 8)
        fn = lambda q, l, pt, kl: decode_mla_attention(q, l, pt, kl, **kw)
        args = (s((n, H, Dl), jnp.bfloat16), pool, s((n, MP), jnp.int32), s((n,), jnp.int32))
    else:
        fn = lambda q, l, pt, qs, ql, kl: prefill_mla_attention(q, l, pt, qs, ql, kl, **kw)
        one = s((1,), jnp.int32)
        args = (s((1, n, H, Dl), jnp.bfloat16), pool, s((1, MP), jnp.int32), one, one, one)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


# -- latent attention at DeepSeek-V3.2's geometry (benchmark cell
# dsv32-docqa-steady): 128 heads, latent rank 512 + a rotary key of 64 (576
# in 640 lanes), 4096 pages of 64 tokens under a page table 576 wide. The
# dense kernels (a step whose rows all hold at most index_topk tokens), and
# the layer itself with its indexer, selection and gather on a prior context
# of 36 k: a decode step of 32 rows and a prefill chunk of 1024.
_DSA = dict(H=128, Dl=640, dc=512, NP=4096, PS=64, MP=576)


@pytest.mark.parametrize("kind, n", [("decode", 32), ("prefill", 256), ("prefill", 1024),
                                     ("decode-selected", 4), ("decode-selected", 32),
                                     ("decode-ling3", 16)])
def test_latent_attention_kernels_compile_for_v5e_at_rank_512(topo, kind, n):
    """`decode-selected`: the selecting arm's call, `index_topk` 2048 gathered
    rows a query as 32 pages of a buffer of their own under the identity
    table. `decode-ling3`: ling-3.0-flash-vl's 32 heads under its page table
    128 wide. Eight 82 KB pages a grid step at every table."""
    from dynamo_tpu.ops.mla_attention import decode_mla_attention, prefill_mla_attention
    from dynamo_tpu.ops.paged_attention import decode_step

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    H, Dl, dc, NP, PS, MP = (_DSA[k] for k in ("H", "Dl", "dc", "NP", "PS", "MP"))
    if kind == "decode-selected":
        NP, MP = n * 32, 32
    elif kind == "decode-ling3":
        H, MP = 32, 128
    kw = dict(dc=dc, scale=0.1352)
    pool = s((NP, PS, 1, Dl), jnp.bfloat16)
    if kind.startswith("decode"):
        assert decode_step((1, H), pool, None, MP, False) == ("by_tiles", 8)
        fn = lambda q, l, pt, kl: decode_mla_attention(q, l, pt, kl, **kw)
        args = (s((n, H, Dl), jnp.bfloat16), pool, s((n, MP), jnp.int32), s((n,), jnp.int32))
    else:
        fn = lambda q, l, pt, qs, ql, kl: prefill_mla_attention(q, l, pt, qs, ql, kl, **kw)
        one = s((1,), jnp.int32)
        args = (s((1, n, H, Dl), jnp.bfloat16), pool, s((1, MP), jnp.int32), one, one, one)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("B, S", [(4, 1), (32, 1), (1, 1024)])
def test_the_indexers_layer_compiles_for_v5e_at_published_widths(topo, B, S):
    """One layer of the cell's configuration through models/mla.py on the
    chip's path: both arms of the step (dense at or below index_topk, the
    selection above it) in one program. A decode step (the cell's 4-row bucket
    and the widest, 32) makes one Mosaic call of the latent attention kernel an
    arm, on the pool or on the gathered buffer, and in the selecting arm the
    select kernel (ops/dsa_select.py) before it, fed the scores as the indexer
    leaves them: no sort over the context, no copy of the scores, no lookup of
    the chosen positions in the page table. A prefill chunk's selecting arm is
    XLA ops (a mask, blocks of queries by blocks of pages under a running
    softmax) and makes none."""
    import json
    import os

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.mla import _mla_attention

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", "deepseek-v3.2.json")) as f:
        c = ModelConfig(**json.load(f)["model"]).with_(n_layers=2)
    on_chip = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    lp = on_chip(jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[0], llama.init_params(c, jax.random.PRNGKey(0))["layers"])))
    kp, ip = on_chip(jax.eval_shape(lambda: llama.make_kv_pool(c, _DSA["NP"], _DSA["PS"])))

    def layer(lp, h, kp, ip, pt, pos, kv):
        safe = jnp.maximum(pos, 0)
        return _mla_attention(c, lp, h, kp, jnp.int32(1), pt, pos, safe, kv,
                              attn_impl="pallas", q_start=safe[:, 0],
                              q_len=jnp.sum(pos >= 0, axis=1), ik_pool=ip)

    comp = jax.jit(layer, donate_argnums=(2, 3)).lower(
        lp, s((B, S, c.dim), jnp.bfloat16), kp, ip, s((B, _DSA["MP"]), jnp.int32),
        s((B, S), jnp.int32), s((B,), jnp.int32)).compile()
    text = comp.as_text()
    kernels = sorted(l.split(" = ")[0].strip().lstrip("%").split(".")[0]
                     for l in text.splitlines() if "tpu_custom_call" in l and " = " in l)
    if S == 1:
        assert kernels == ["decode_mla_attention", "decode_mla_attention", "dsa_select"]
        C = _DSA["MP"] * _DSA["PS"]
        ops = [l.strip() for l in text.splitlines() if " = " in l]
        assert not [l[:160] for l in ops if " sort(" in l]
        assert not [l[:160] for l in ops if f"[{B},{C}]" in l and " copy(" in l]
        assert not [l[:160] for l in ops if " gather(" in l
                    and ("attn.select" in l or "take_along_axis" in l)]
    else:
        assert kernels == ["prefill_mla_attention"]
    assert comp.memory_analysis().temp_size_in_bytes < 4 << 30


# -- routed experts over a work list of hit experts (ops/moe_experts.py) at
# the benchmark cell's geometry (mistral4-chat-steady: dim 4096, experts of
# width 2048, 32 held of a router 128 wide, 4 a token, six layers stacked)
# and at a whole layer of 8 wide experts, 2 a token (Mixtral's widths).
# name -> (rows, E, F, held, first, k, L)
_EXPERT_SHAPES = {
    "cell-t8": (8, 4096, 2048, 32, 32, 4, 6),
    "cell-t16": (16, 4096, 2048, 32, 32, 4, 6),
    "cell-t32": (32, 4096, 2048, 32, 32, 4, 6),
    "whole8-t8": (8, 4096, 14336, 8, 0, 2, 2),
    # ling-3.0-flash-vl's decode bucket of 64 rows (moe_experts.row_bound: a
    # router of 512 with 8 picks still leaves experts unread at 64 rows)
    "ling3-t64": (64, 2560, 768, 32, 32, 8, 16),
}


def _slab_ops(text, held, E, F):
    """HLO instructions whose result is one layer's slab of an expert stack
    ([held, E, F] or [held, F, E], with or without a leading 1): a copy or
    a slice of the stack in front of the kernel (ROADMAP S6a)."""
    import re

    slab = re.compile(
        rf"= bf16\[(1,)?{held},({E},{F}|{F},{E})\]\S* (?!parameter)")
    return [l.strip()[:160] for l in text.splitlines() if slab.search(l)]


@pytest.mark.parametrize("name", list(_EXPERT_SHAPES))
def test_routed_experts_kernel_compiles_for_v5e(topo, name):
    """The list built in XLA from the picks, then one Mosaic call on the
    stacked weights at a traced layer: no slab of a stack in front of it."""
    from dynamo_tpu.ops.moe_experts import hit_work_list, routed_experts

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    T, E, F, held, first, k, L = _EXPERT_SHAPES[name]

    def fn(x, sel, w, valid, wg, wu, wd, layer):
        work, n_work, wcol = hit_work_list(sel, w, valid, first, held)
        return routed_experts(x, work, n_work, wcol, wg, wu, wd, layer)

    bf = jnp.bfloat16
    text = jax.jit(fn).lower(
        s((T, E), bf), s((T, k), jnp.int32), s((T, k), bf), s((T,), jnp.bool_),
        s((L, held, E, F), bf), s((L, held, E, F), bf), s((L, held, F, E), bf),
        s((), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "custom-call(" in text and "%routed_experts" in text  # no `attention` in its name
    assert _slab_ops(text, held, E, F) == []


def test_decode_loop_reads_the_expert_stacks_in_place(topo):
    """The compiled decode loop of the cell's configuration (two of its
    six layers, four fused steps, 32 rows): the expert kernel beside the
    latent attention kernel in the layer scan, and no copy or slice of an
    expert stack anywhere in the program. Handed the scan's slice of a
    stack instead, the same kernel gets a copy of the slab in front of it,
    which is how the check can see one."""
    import json
    import os
    from functools import partial

    from dynamo_tpu.engine.model_runner import _decode_loop
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.moe_experts import hit_work_list, routed_experts

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", "mistral-small-4-119b.json")) as f:
        c = ModelConfig(**json.load(f)["model"]).with_(n_layers=2)
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(c, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    pools = on_chip(jax.eval_shape(
        lambda: llama.make_kv_pool(c, 768, 64, dtype=jnp.bfloat16)))
    B, MP, f32, i32 = 32, 64, jnp.float32, jnp.int32
    samp = SamplingParams(s((B,), f32), s((B,), i32), s((B,), f32), s((B, 2), jnp.uint32),
                          s((B,), f32), s((B,), f32), s((B,), f32))
    text = jax.jit(partial(_decode_loop, c, "pallas", None, 4, -1)).lower(
        params, s((B,), i32), s((B + B * MP + 1,), i32), None, None, None,
        *pools, samp).compile().as_text()
    held, E, F = c.experts_held, c.dim, c.moe_ffn_dim
    kernels = {l.split(" = ")[0].strip().lstrip("%").split(".")[0]
               for l in text.splitlines() if "tpu_custom_call" in l and " = " in l}
    assert kernels == {"decode_mla_attention", "routed_experts"}
    assert _slab_ops(text, held, E, F) == []

    def sliced(x, sel, w, valid, wg, wu, wd):
        def layer(x, lp):  # the kernel on what a layer scan slices
            work, n_work, wcol = hit_work_list(sel, w, valid, c.expert_first, held)
            y = routed_experts(x, work, n_work, wcol, *(a[None] for a in lp), 0)
            return x + y.astype(x.dtype), None
        return jax.lax.scan(layer, x, (wg, wu, wd))[0]

    bf = jnp.bfloat16
    text = jax.jit(sliced).lower(
        s((B, E), bf), s((B, 4), i32), s((B, 4), bf), s((B,), jnp.bool_),
        s((2, held, E, F), bf), s((2, held, E, F), bf), s((2, held, F, E), bf),
    ).compile().as_text()
    assert _slab_ops(text, held, E, F)


# -- the state-space kernels and a hybrid model's step programs --------------


@pytest.mark.parametrize("kernel", ["ssm_update", "ssm_scan"])
@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_state_space_kernels_compile_for_v5e_at_jamba_widths(topo, kernel, state_dtype):
    """ai21-jamba2-3b's recurrence: 26 layers x 65 slots of [16, 5120]
    states, 64 decode rows / a flat step of 320 tokens."""
    from dynamo_tpu.ops import ssm

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    N, d, f32, i32 = 16, 5120, jnp.float32, jnp.int32
    pool = s((26, 65, N) + ssm.state_shape(d), state_dtype)
    T = 64 if kernel == "ssm_update" else 320
    ops = (s((T, d), f32), s((T, d), f32), s((T, N), f32), s((T, N), f32), s((N, d), f32))
    if kernel == "ssm_update":
        args = (pool, s((), i32), s((T,), i32), s((T,), jnp.bool_), s((T,), jnp.bool_)) + ops
    else:
        args = (pool, s((), i32), s((T,), i32), s((T,), i32)) + ops
    text = jax.jit(getattr(ssm, kernel), donate_argnums=(0,)).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_a_hybrid_models_step_programs_compile_for_v5e(topo, monkeypatch):
    """ai21-jamba2-3b whole, as its cell runs it: the decode loop (64 rows, 4
    fused steps) and the ragged step (320 tokens). The attention kernels at
    its geometry (20 query heads on one KV head of 128: no multiple of their
    row block), the state kernels beside them, and the state pool read in
    place: no slice or copy of it in front of a kernel. Nor of the KV pool:
    at one KV head the kernels take the stack as the programs carry it.
    Handed the 5-d operand instead (the by-heads routine, which every call
    at one head took until PR 39), the decode loop converts both pools
    whole in front of the kernel, which is how the check can see one."""
    import re
    from functools import partial

    from dynamo_tpu.engine.model_runner import _decode_loop, _ragged_step
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.models import jamba, llama
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    def samp(B):
        return SamplingParams(s((B,), f32), s((B,), i32), s((B,), f32), s((B, 2), jnp.uint32),
                              s((B,), f32), s((B,), f32), s((B,), f32))

    def kernels(text):
        return {l.split(" = ")[0].strip().lstrip("%").split(".")[0]
                for l in text.splitlines() if "tpu_custom_call" in l and " = " in l}

    c = get_config("ai21-jamba2-3b")
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(c, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    pools = on_chip(jax.eval_shape(lambda: llama.make_kv_pool(c, 2880, 64, dtype=jnp.bfloat16)))
    state = on_chip(jax.eval_shape(lambda: jamba.make_state_pool(c, 65)))
    assert pools[0].shape[0] == 2  # the attention layers alone
    B, MP, f32, i32 = 64, 64, jnp.float32, jnp.int32
    jit = partial(jax.jit, donate_argnames=("state",))

    def decode_loop():
        return jit(partial(_decode_loop, c, "pallas", None, 4, -1), donate_argnums=(6, 7)).lower(
            params, s((B,), i32), s((B + B * MP + 1,), i32), None, None, None, *pools, samp(B),
            state=state, slots=s((B,), i32)).compile().as_text()

    text = decode_loop()
    assert kernels(text) == {"decode_paged_attention", "ssm_update"}
    # one layer's states of every slot, sliced or copied out of the pool
    slab = re.compile(r"= f32\[(1,)?65,16,40,128\]\S* (dynamic-slice|slice|copy)\(")
    assert not slab.search(text)
    # the whole KV stack, or its 4-d view, copied (converted between layouts)
    kv_copy = re.compile(r"= bf16\[2,2880,64,(1,)?128\]\S* copy\(")
    assert not kv_copy.search(text)
    from dynamo_tpu.ops import paged_attention as pa_ops

    with monkeypatch.context() as m:
        m.setattr(pa_ops, "page_routine", lambda *a: "by_heads")
        jax.clear_caches()  # the kernel's wrapper is traced again
        assert len(kv_copy.findall(decode_loop())) == 2  # K and V
    jax.clear_caches()
    T = 320
    md = build_ragged_metadata([1] * 8 + [100, 150], [5] * 8 + [0, 0], [6] * 8 + [100, 150],
                               [[1]] * 8 + [[2, 3], [4, 5, 6]], T, q_block=8, max_pages=MP)
    SEG, V = md["seg_page_table"].shape[0], c.vocab_size
    text = jit(partial(_ragged_step, c, "pallas", None), donate_argnums=(9, 10)).lower(
        params, s((1, T), i32), s((1, T), i32), s((T, MP), i32), s((T,), i32),
        s(md["seg_page_table"].shape, i32), s((SEG,), i32), s(md["meta"].shape, i32),
        s((SEG,), i32), *pools, samp(SEG), s((SEG,), i32), s((SEG,), i32), s((), i32),
        s((SEG, V), jnp.bool_), s((SEG, V), f32), state=state,
        seg_slots=s((3, SEG), i32)).compile().as_text()
    assert kernels(text) == {"ragged_paged_attention", "ssm_scan"}
    assert not slab.search(text)
    assert not kv_copy.search(text)


def test_a_window_pool_models_decode_loop_reads_both_pools_in_place(topo):
    """mimo-v2-flash as its cell runs it: the decode loop (16 rows, 4 fused
    steps, a page table 96 wide) over the cell's pools, 4096 global pages
    and the engine's 165 window pages. A kernel a kind of layer beside the
    expert kernel, each reading a KV head out of the token-major page as
    the step program carries it: no copy and no slice of either pool's
    shape, nor of a 4-d view of one, anywhere in the program. Handed the 4-d
    view `[L, NP, PS, Hk * D]` instead (a head a lane-aligned slice of a
    row), XLA lays the whole stack out again in front of the kernel (a
    `reshape` that is no bitcast): the pools lie `T(4,128)(2,1)` /
    `T(8,128)(2,1)`, heads on sublanes, which is how the check can see one."""
    import json
    import os
    import re
    from functools import partial

    from dynamo_tpu.engine.model_runner import _decode_loop
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.models import llama, mimo
    from dynamo_tpu.models.config import ModelConfig

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", "mimo-v2-flash.json")) as f:
        cfg = json.load(f)
    c = ModelConfig(**cfg["model"])
    NP, NPW, PS = cfg["server_flags"]["num-pages"], 165, cfg["server_flags"]["page-size"]
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(c, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    pools = on_chip(jax.eval_shape(lambda: llama.make_kv_pool(c, NP, PS, dtype=jnp.bfloat16)))
    state = on_chip(jax.eval_shape(lambda: mimo.make_window_pool(c, NPW, PS)))
    assert pools[0].shape == (2, NP, PS, 4, 256) and pools[1].shape == (2, NP, PS, 4, 128)
    assert state["k"].shape == (9, NPW, PS, 8, 256) and state["v"].shape == (9, NPW, PS, 8, 128)
    B, MP, f32, i32 = 16, 96, jnp.float32, jnp.int32
    samp = SamplingParams(s((B,), f32), s((B,), i32), s((B,), f32), s((B, 2), jnp.uint32),
                          s((B,), f32), s((B,), f32), s((B,), f32))
    text = jax.jit(partial(_decode_loop, c, "pallas", None, 4, -1), donate_argnums=(6, 7),
                   donate_argnames=("state",)).lower(
        params, s((B,), i32), s((B + B * MP + 1,), i32), None, None, None, *pools, samp,
        state=state, slots=s((B, MP), i32)).compile().as_text()
    kernels = {l.split(" = ")[0].strip().lstrip("%").split(".")[0]
               for l in text.splitlines() if "tpu_custom_call" in l and " = " in l}
    assert kernels == {"decode_paged_attention", "window_attention_decode", "routed_experts"}
    # a pool, one layer of it or a page run of it, 5-d or as a 4-d view
    moved = re.compile(
        rf"= bf16\[(\d+,)?(\d+,)?{PS},(4,(256|128)|8,(256|128)|1024|512|2048)\]\S* "
        r"(copy|reshape|dynamic-slice|slice)\(")
    assert [l.strip()[:160] for l in text.splitlines() if moved.search(l)] == []

    def viewed(k, at):  # a kernel on the 4-d view: head 0 of one page
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def head0(at_ref, k_ref, o_ref):
            o_ref[...] = k_ref[:, :256]

        out = pl.pallas_call(
            head0,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec((None, None, PS, 4 * 256),
                                       lambda i, at: (at[0], at[1], 0, 0))],
                out_specs=pl.BlockSpec((PS, 256), lambda i, at: (0, 0))),
            out_shape=jax.ShapeDtypeStruct((PS, 256), k.dtype),
        )(at, k.reshape(k.shape[:3] + (-1,)))
        return out, k

    text = jax.jit(viewed, donate_argnums=(0,)).lower(
        pools[0], s((2,), i32)).compile().as_text()
    assert [l for l in text.splitlines() if moved.search(l)]


def test_a_window_pool_models_ragged_step_reads_both_pools_in_place(topo):
    """mimo-v2-flash's mixed step as its cell runs it (T bucket 288, a page
    table 96 wide, the cell's pools): the ragged kernel under its two names,
    two global calls and the window layers' in their scan, each taking its
    step's pages as the pool holds them; no copy, slice or reshape of either
    pool's shape, a layer's or a 4-d view's, anywhere in the program (the q
    blocks' own transposes are `[36, 64, 8, 256]`: no pool's shape)."""
    import json
    import os
    import re
    from functools import partial

    from dynamo_tpu.engine.model_runner import _ragged_step
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.models import llama, mimo
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.ragged_paged_attention import ragged_seg_cap, ragged_work_cap

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", "mimo-v2-flash.json")) as f:
        cfg = json.load(f)
    c = ModelConfig(**cfg["model"])
    NP, NPW, PS = cfg["server_flags"]["num-pages"], 165, cfg["server_flags"]["page-size"]
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(c, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    pools = on_chip(jax.eval_shape(lambda: llama.make_kv_pool(c, NP, PS, dtype=jnp.bfloat16)))
    state = on_chip(jax.eval_shape(lambda: mimo.make_window_pool(c, NPW, PS)))
    T, MP, f32, i32 = 288, 96, jnp.float32, jnp.int32
    SEG, V = ragged_seg_cap(T), c.vocab_size
    samp = SamplingParams(s((SEG,), f32), s((SEG,), i32), s((SEG,), f32), s((SEG, 2), jnp.uint32),
                          s((SEG,), f32), s((SEG,), f32), s((SEG,), f32))
    text = jax.jit(partial(_ragged_step, c, "pallas", None), donate_argnums=(9, 10),
                   donate_argnames=("state",)).lower(
        params, s((1, T), i32), s((1, T), i32), s((T, MP), i32), s((T,), i32),
        s((SEG, MP), i32), s((SEG,), i32), s((5, ragged_work_cap(T)), i32), s((SEG,), i32),
        *pools, samp, s((SEG,), i32), s((SEG,), i32), s((), i32), s((SEG, V), jnp.bool_),
        s((SEG, V), f32), state=state,
        seg_slots=(s((T, MP), i32), s((SEG, MP), i32))).compile().as_text()
    kernels = {l.split(" = ")[0].strip().lstrip("%").split(".")[0]
               for l in text.splitlines() if "tpu_custom_call" in l and " = " in l}
    assert kernels == {"ragged_paged_attention", "window_attention_ragged"}
    moved = re.compile(
        rf"= bf16\[((2|9),)?({NP}|{NPW}),{PS},(4,(256|128)|8,(256|128)|1024|512|2048)\]\S* "
        r"(copy|reshape|dynamic-slice|slice)\(")
    assert [l.strip()[:160] for l in text.splitlines() if moved.search(l)] == []


# -- the latent pool read in place (ops/mla_attention.py) ---------------------
# name -> (benchmark configuration, layers kept (None: all), program)
_LATENT_PROGRAMS = {
    # ling-3.0-flash-vl whole: 3 pool layers, rank 512 + 64 in 640 lanes, the
    # cell's 4096 pages; 16 decode rows, and its 512-token prefill chunk
    "ling3-decode": ("ling-3.0-flash-vl", None, "decode"),
    "ling3-chunk512": ("ling-3.0-flash-vl", None, "chunk"),
    # mistral-small-4-119b, two of its layers: rank 256 + 64, 768 pages
    "mistral4-decode": ("mistral-small-4-119b", 2, "decode"),
    "mistral4-chunk512": ("mistral-small-4-119b", 2, "chunk"),
}


@pytest.mark.parametrize("name", list(_LATENT_PROGRAMS))
def test_a_latent_models_step_programs_read_the_latent_pool_in_place(topo, name, monkeypatch):
    """The decode loop (4 fused steps) and the 512-token prefill chunk of a
    model with latent attention, compiled for the described v5e: the latent
    kernel takes the pool as the layer scan carries it, at a scalar-prefetched
    layer, so no instruction's result is one layer's slab `bf16[NP,PS,(1,)Dl]`
    (a `dynamic-slice_bitcast_fusion` of 335 MB in front of every latent layer
    of ling-3.0-flash-vl's step until PR 51), and none inside the loops is the
    whole pool. (At 320 lanes the pool is laid out anew at the entry and the
    exit of a step program, before and after this PR: ModelConfig.mla_pool_dim
    says why; a pool of whole 128-lane rows is not.) The same decode loop with
    `k_pool[l_idx]` handed to the kernel does have the slab, which is how the
    check can see one. The decode kernel walks a list of the rows' live pages
    (since PR 52), which hangs on the lengths alone: the decode loop builds it
    once a step, in the step loop, and no instruction of it (`attn.walk` in
    the metadata) sits inside the layer scan; with the walk left to the
    kernel's wrapper, inside the scan, the same check finds it there."""
    import json
    import os
    import re
    from functools import partial

    from dynamo_tpu.engine.model_runner import _decode_loop, _forward, _side_forward
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.models import ling, llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import mla_attention as mla_ops

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: s(a.shape, a.dtype), tree)

    config, layers, program = _LATENT_PROGRAMS[name]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", config + ".json")) as f:
        cfg = json.load(f)
    c = ModelConfig(**cfg["model"])
    if layers:
        c = c.with_(n_layers=layers)
    flags = cfg["server_flags"]
    NP, PS = flags["num-pages"], flags["page-size"]
    MP, f32, i32 = flags["max-seq-len"] // PS, jnp.float32, jnp.int32
    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(c, jax.random.PRNGKey(0), dtype=jnp.bfloat16)))
    pools = on_chip(jax.eval_shape(lambda: llama.make_kv_pool(c, NP, PS, dtype=jnp.bfloat16)))
    L, Dl = c.kv_layers, c.mla_pool_dim
    assert pools[0].shape == (L, NP, PS, 1, Dl)
    side = {}
    if c.is_kda:
        side = {"state": on_chip(jax.eval_shape(lambda: ling.make_state_pool(c, 65)))}
    skw = {"donate_argnames": ("state",)} if side else {}

    def compiled():
        if program == "decode":
            B = 16
            samp = SamplingParams(s((B,), f32), s((B,), i32), s((B,), f32),
                                  s((B, 2), jnp.uint32), s((B,), f32), s((B,), f32), s((B,), f32))
            slots = {"slots": s((B,), i32)} if side else {}
            return jax.jit(partial(_decode_loop, c, "pallas", None, 4, -1),
                           donate_argnums=(6, 7), **skw).lower(
                params, s((B,), i32), s((B + B * MP + 1,), i32), None, None, None,
                *pools, samp, **side, **slots).compile().as_text()
        fwd = (partial(_side_forward, c, chunk_picks=True) if side else partial(_forward, c))
        slots = {"slots": s((1,), i32)} if side else {}
        return jax.jit(fwd, donate_argnums=(3, 4), static_argnames=("attn_impl", "mesh"),
                       **skw).lower(
            params, s((1, 512), i32), s((1, 512), i32), *pools, s((1, MP), i32),
            s((1,), i32), s((), i32), attn_impl="pallas", mesh=None,
            **side, **slots).compile().as_text()

    slab = re.compile(rf"= bf16\[(1,)?{NP},{PS},(1,)?{Dl}\]\S* "
                      r"(copy|reshape|dynamic-slice|slice|fusion)\(")
    whole = re.compile(rf"= bf16\[{L},{NP},{PS},(1,)?{Dl}\]\S* "
                       r"(copy|reshape|dynamic-slice|slice)\(")

    def moved(text):
        """(a layer's slab anywhere, the whole pool inside a loop or a
        branch, the whole pool in the entry computation)."""
        slabs, inner, outer, entry = [], [], [], False
        for l in text.splitlines():
            if l.startswith(("ENTRY", "}")):
                entry = l.startswith("ENTRY")
            if slab.search(l):
                slabs.append(l.strip()[:160])
            elif whole.search(l):
                (outer if entry else inner).append(l.strip()[:160])
        return slabs, inner, outer

    text = compiled()
    kernels = {l.split(" = ")[0].strip().lstrip("%").split(".")[0]
               for l in text.splitlines() if "tpu_custom_call" in l and " = " in l}
    kernel = "decode_mla_attention" if program == "decode" else "prefill_mla_attention"
    others = {"routed_experts"} if program == "decode" else set()
    if c.is_kda:
        others |= {"kda_update"} if program == "decode" else {"kda_chunk"}
    assert kernels == {kernel} | others
    slabs, inner, outer = moved(text)
    assert slabs == [] and inner == []
    if Dl % 128 == 0:
        assert outer == []

    if program != "decode":
        return

    def walks_built(text):
        """(instructions that build a walk's lists (`latent_walk` names
        them `attn.walk`), those of them inside a loop inside the step
        loop: the layer scan)."""
        built = [l for l in text.splitlines() if "attn.walk" in l and " = " in l]
        return built, [l.strip()[:160] for l in built
                       if l.count("while/body") > 1]

    # the decode kernel's lists of live pages hang on the lengths alone:
    # built once a step, in the step loop, and carried into the layer scan
    built, in_scan = walks_built(text)
    assert built and in_scan == []
    orig = getattr(mla_ops, kernel)

    def sliced(q, pool, *rest, **kw):  # the operand until PR 51
        *rows, layer = rest
        return orig(q, jax.tree.map(lambda a: a[layer], pool), *rows, **kw)

    monkeypatch.setattr(mla_ops, kernel, sliced)
    assert moved(compiled())[0]

    def relisted(*args, work=None, **kw):  # the walk built where it is used
        return orig(*args, **kw)

    monkeypatch.setattr(mla_ops, kernel, relisted)
    assert walks_built(compiled())[1]


# -- the delta-rule kernels (ops/kda.py) ---------------------------------------


@pytest.mark.parametrize("kernel", ["kda_update", "kda_chunk"])
def test_delta_rule_kernels_compile_for_v5e_at_ling_widths(topo, kernel):
    """ling-3.0-flash-vl's KDA layers: 32 heads of 128 x 128 float32, 64
    decode rows on a pool of 15 layers x 65 slots (read and written in place:
    one custom call, no copy of the pool); a chunk of 512 tokens, whose walk
    over 8 blocks of 64 is the one custom call among XLA matmuls."""
    from dynamo_tpu.ops import kda

    one_chip = SingleDeviceSharding(topo.devices[0])

    def s(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    B, H, d, T = 64, 32, 128, 512
    if kernel == "kda_update":
        vec = s((B, H, d))
        text = jax.jit(kda.kda_update, donate_argnums=(0,)).lower(
            s((15, 65, H, d, d)), s((), jnp.int32), s((B,), jnp.int32),
            s((B,), jnp.bool_), s((B,), jnp.bool_), vec, vec, vec, vec,
            s((B, H))).compile().as_text()
        assert "f32[15,65,32,128,128]{4,3,2,1,0} copy(" not in text
    else:
        tok = s((T, H, d))
        text = jax.jit(kda.kda_chunk).lower(
            s((H, d, d)), tok, tok, tok, tok, s((T, H))).compile().as_text()
    assert text.count("tpu_custom_call") == 1
