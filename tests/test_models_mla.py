"""MLA (multi-head latent attention, DeepSeek V2/V3/R1 family — the
reference's flagship BASELINE model, recipes/deepseek-r1): absorbed-form
attention over a per-token latent cache, through the same forward, pool,
engine and parallel machinery as the GQA family."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.runtime.context import Context


def _runner(name, mesh_config=None, **kw):
    return ModelRunner(
        get_config(name), mesh_config, num_pages=64, page_size=4,
        max_pages_per_seq=16, decode_buckets=(1, 2, 4),
        prefill_buckets=(8, 16), seed=13, **kw,
    )


def _generate(runner, prompt, n=5):
    async def run():
        engine = InferenceEngine(runner, max_batch=4, chunk_size=16)
        engine.start()
        try:
            toks = []
            req = {"token_ids": prompt, "sampling": {"temperature": 0.0},
                   "stop": {"max_tokens": n, "stop_ids": []}}
            async for item in engine.generate(req, Context()):
                toks.extend(item["token_ids"])
                if item["finish_reason"]:
                    break
            return toks
        finally:
            engine.stop()

    return asyncio.run(run())


def test_mla_cache_is_latent_sized():
    c = get_config("tiny-mla")
    k_pool, v_pool = llama.make_kv_pool(c, 8, 4)
    assert k_pool.shape == (c.n_layers, 8, 4, 1, c.mla_cache_dim)
    assert v_pool.shape[-2:] == (1, 1)  # placeholder
    # the architecture's point: far smaller than the full-head cache
    gqa = get_config("tiny")
    kg, vg = llama.make_kv_pool(gqa, 8, 4)
    assert k_pool.nbytes + v_pool.nbytes < kg.nbytes + vg.nbytes


def test_mla_prefill_decode_parity():
    """Logits for position t must be identical whether t arrives in one
    big prefill or via prefill + incremental decode steps (the cache
    faithfully reproduces attention over the full context)."""
    c = get_config("tiny-mla")
    p = llama.init_params(c, jax.random.PRNGKey(0))
    toks = [5, 9, 2, 7, 1, 8, 3, 4]
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]

    # one-shot full prefill
    k1, v1 = llama.make_kv_pool(c, 8, 4)
    full, _, _ = llama.forward(
        c, p, jnp.asarray([toks]), jnp.asarray([list(range(8))]),
        k1, v1, pt, jnp.asarray([8]),
    )

    # prefill 5, then decode 3 one at a time
    k2, v2 = llama.make_kv_pool(c, 8, 4)
    out, k2, v2 = llama.forward(
        c, p, jnp.asarray([toks[:5]]), jnp.asarray([list(range(5))]),
        k2, v2, pt, jnp.asarray([5]),
    )
    np.testing.assert_allclose(
        np.asarray(out[0, :5]), np.asarray(full[0, :5]), rtol=2e-2, atol=2e-2
    )
    for t in range(5, 8):
        out, k2, v2 = llama.forward(
            c, p, jnp.asarray([[toks[t]]]), jnp.asarray([[t]]),
            k2, v2, pt, jnp.asarray([t + 1]),
        )
        np.testing.assert_allclose(
            np.asarray(out[0, 0]), np.asarray(full[0, t]), rtol=2e-2, atol=2e-2
        )


def test_mla_q_compression_variant():
    c = get_config("tiny-mla-q")
    p = llama.init_params(c, jax.random.PRNGKey(1))
    assert "wq_lat" in p["layers"] and "wq" not in p["layers"]
    k, v = llama.make_kv_pool(c, 8, 4)
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]
    logits, _, _ = llama.forward(
        c, p, jnp.asarray([[1, 2, 3, 4]]), jnp.asarray([[0, 1, 2, 3]]),
        k, v, pt, jnp.asarray([4]),
    )
    assert np.isfinite(np.asarray(logits)).all()


def test_mla_engine_greedy_deterministic():
    toks = _generate(_runner("tiny-mla"), [5, 3, 8, 1, 9, 2])
    toks2 = _generate(_runner("tiny-mla"), [5, 3, 8, 1, 9, 2])
    assert toks == toks2 and len(toks) == 5


def test_mla_moe_engine_generates():
    toks = _generate(_runner("tiny-mla-moe"), [4, 4, 2, 9, 6])
    assert len(toks) == 5


def test_mla_prefix_cache_consistency():
    """Prefix-cache hits must not change greedy output (the latent pool
    rides the same paging machinery as GQA KV)."""
    runner = _runner("tiny-mla")

    async def run():
        engine = InferenceEngine(runner, max_batch=4, chunk_size=16)
        engine.start()
        try:
            base = [11, 12, 13, 14, 15, 16, 17, 18]

            async def gen():
                toks = []
                req = {"token_ids": base, "sampling": {"temperature": 0.0},
                       "stop": {"max_tokens": 4, "stop_ids": []}}
                async for item in engine.generate(req, Context()):
                    toks.extend(item["token_ids"])
                    if item["finish_reason"]:
                        break
                return toks

            a = await gen()
            b = await gen()  # second run hits the cached prefix pages
            assert a == b and len(a) == 4
        finally:
            engine.stop()

    asyncio.run(run())


def test_mla_kv_wire_roundtrip():
    """Disagg/tiering transfer for MLA: the asymmetric (latent k, stub v)
    pools export/import through the wire payload without shape lies —
    kv_page_shape advertises the REAL latent geometry."""
    r = _runner("tiny-mla")
    c = r.config
    assert r.kv_page_shape == (c.n_layers, 4, 1, c.mla_cache_dim)
    # write some context so exported pages are non-trivial
    logits = r.prefill([5, 9, 2, 7], 0, [0, 1], prior_len=0)
    payload = r.export_pages([0, 1])
    assert payload["shape"][-1] == c.mla_cache_dim
    assert payload["v_shape"][-1] == 1
    r2 = _runner("tiny-mla")
    r2.import_pages([3, 4], 0, payload)  # validates against its geometry
    import numpy as np

    k2 = np.asarray(r2.k_pool[:, 3:5])
    k1 = np.asarray(r.k_pool[:, 0:2])
    np.testing.assert_array_equal(k1, k2)


def test_mla_tp_mesh_parity():
    """TP=2 over the CPU mesh must reproduce single-device greedy decode
    (latent pool replicates; heads shard via GSPMD)."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    if len(jax.devices()) < 2:
        pytest.skip("needs the 8-device CPU mesh")
    solo = _generate(_runner("tiny-mla"), [7, 2, 9, 4, 1])
    tp = _generate(
        _runner("tiny-mla", mesh_config=MeshConfig(model=2)), [7, 2, 9, 4, 1]
    )
    assert solo == tp


def test_rope_scaling_yarn_and_llama3():
    """rope_inv_freq: yarn interpolates low-frequency dims by 1/factor and
    keeps high-frequency dims; llama3 does the same band-wise; yarn's
    mscale lifts cos/sin magnitude and the attention score scale."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import (
        attn_score_scale, rope, rope_inv_freq, _yarn_mscale,
    )

    base = np.asarray(rope_inv_freq(None, 64, 10000.0))
    yarn_cfg = ModelConfig(
        rope_scaling="yarn", rope_factor=40.0, rope_orig_max_seq=4096,
        rope_mscale=1.0, rope_mscale_all_dim=1.0, max_seq_len=163840,
    )
    y = np.asarray(rope_inv_freq(yarn_cfg, 64, 10000.0))
    assert np.allclose(y[0], base[0], rtol=1e-5)  # highest freq kept
    assert np.allclose(y[-1], base[-1] / 40.0, rtol=1e-5)  # lowest interp
    l3_cfg = ModelConfig(
        rope_scaling="llama3", rope_factor=8.0, rope_orig_max_seq=8192,
        max_seq_len=131072,
    )
    l3 = np.asarray(rope_inv_freq(l3_cfg, 128, 500000.0))
    b2 = np.asarray(rope_inv_freq(None, 128, 500000.0))
    assert np.allclose(l3[0], b2[0]) and np.allclose(l3[-1], b2[-1] / 8.0)
    assert ((l3 <= b2 + 1e-12) & (l3 >= b2 / 8.0 - 1e-12)).all()

    # yarn mscale: attention scale gains mscale^2; cos/sin magnitude only
    # when mscale != mscale_all_dim
    m = _yarn_mscale(40.0, 1.0)
    assert abs(attn_score_scale(yarn_cfg, 64) - 64**-0.5 * m * m) < 1e-9
    x = jnp.ones((1, 1, 1, 8), jnp.float32)
    pos = jnp.asarray([[0]])
    r_scaled = np.asarray(rope(x, pos, 1e4, config=yarn_cfg))
    # mscale == mscale_all_dim -> ratio 1: rope output matches unscaled
    r_plain = np.asarray(rope(x, pos, 1e4))
    np.testing.assert_allclose(r_scaled, r_plain, rtol=1e-6)


def test_group_limited_routing():
    """DeepSeek-V3 n_group/topk_group: experts outside the selected
    groups are never picked, even when their gates score highest."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.moe_dispatch import router_topk

    # 8 experts in 4 groups of 2; token strongly prefers expert 0 (group
    # 0) and expert 7 (group 3) but groups 1+2 score higher on AVERAGE
    logits = jnp.asarray([[5.0, 4.9, 4.5, 4.4, 4.5, 4.4, -9.0, -9.0]])
    w, sel = router_topk(
        logits, 2, "sigmoid", n_groups=4, topk_groups=2,
    )
    picked = set(np.asarray(sel)[0].tolist())
    # groups 0 (5.0+4.9) and 1 (4.5+4.4) win; experts 6/7 banned
    assert picked <= {0, 1, 2, 3}
    assert 0 in picked
    # bias shifts selection into another group but weights stay unbiased
    bias = jnp.asarray([0., 0., 0., 0., 0., 0., 20.0, 20.0])
    w2, sel2 = router_topk(
        logits, 2, "sigmoid", bias=bias, n_groups=4, topk_groups=2,
    )
    picked2 = set(np.asarray(sel2)[0].tolist())
    assert {6, 7} & picked2
    gates = np.asarray(jax.nn.sigmoid(logits))[0]
    for j, e in enumerate(np.asarray(sel2)[0]):
        raw_w = np.asarray(w2)[0, j] * np.asarray(w2)[0].sum() / np.asarray(w2)[0].sum()
    # weights derive from unbiased gates (normalized)
    expect = gates[np.asarray(sel2)[0]]
    expect = expect / expect.sum()
    np.testing.assert_allclose(np.asarray(w2)[0], expect, rtol=1e-5)


def test_mla_int8_latent_cache_close_to_bf16():
    """int8 latent KV (per-vector scales; halves V3's cache again): the
    quantized-pool forward must stay within the int8 rounding envelope
    of the bf16 pool on identical weights, and serve e2e through the
    engine (prefill chunks + fused decode + prefix cache)."""
    import jax.numpy as jnp

    from dynamo_tpu.models import llama

    c = get_config("tiny-mla")
    p = llama.init_params(c, jax.random.PRNGKey(4))
    toks = [5, 9, 2, 7, 1, 3, 8, 4]
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]

    def logits_with(kv_quantize):
        k, v = llama.make_kv_pool(c, 8, 4, kv_quantize=kv_quantize)
        out, k, v = llama.forward(
            c, p, jnp.asarray([toks]),
            jnp.asarray([list(range(len(toks)))]), k, v, pt,
            jnp.asarray([len(toks)]),
        )
        # one decode step over the quantized context
        out2, _, _ = llama.forward(
            c, p, jnp.asarray([[6]]), jnp.asarray([[len(toks)]]), k, v, pt,
            jnp.asarray([len(toks) + 1]),
        )
        return np.asarray(out, np.float32), np.asarray(out2, np.float32)

    ref1, ref2 = logits_with(None)
    q1, q2 = logits_with("int8")
    assert np.abs(q1 - ref1).max() < 0.15, np.abs(q1 - ref1).max()
    assert np.abs(q2 - ref2).max() < 0.15, np.abs(q2 - ref2).max()


async def test_mla_int8_engine_and_transfer_roundtrip():
    """tiny-mla with kv_quantize=int8 serves through the engine, and the
    dense-wire transfer contract holds: export dequantizes, import
    re-quantizes, greedy decode over imported context still works."""
    runner = _runner("tiny-mla", kv_quantize="int8")
    out = await _generate_async(runner, [4, 2, 4, 2, 7, 5], n=5)
    assert len(out) == 5
    payload = runner.export_pages([0, 1])
    assert payload["dtype"] in ("bfloat16", "float32")
    runner.import_pages([4, 5], 0, payload)
    back = runner.export_pages([4, 5])
    import ml_dtypes

    a = np.frombuffer(payload["k"], dtype=ml_dtypes.bfloat16)
    b = np.frombuffer(back["k"], dtype=ml_dtypes.bfloat16)
    # one extra int8 round trip of quantization error, bounded
    assert np.abs(a.astype(np.float32) - b.astype(np.float32)).max() < 0.1


async def _generate_async(runner, prompt, n=5):
    engine = InferenceEngine(runner, max_batch=4, chunk_size=16)
    engine.start()
    try:
        toks = []
        async for item in engine.generate(
            {"token_ids": prompt, "sampling": {"temperature": 0.0},
             "stop": {"max_tokens": n, "stop_ids": []}},
            Context(),
        ):
            assert item.get("finish_reason") != "error", item
            toks.extend(item["token_ids"])
            if item["finish_reason"]:
                break
        return toks
    finally:
        engine.stop()


# -- the latent kernels on the layer-stacked pool (ops/mla_attention.py) ------
# The pool operand is the pool as the layer scan carries it,
# [L, NP, PS, 1, Dl], read at a traced `layer`; one layer's [NP, PS, 1, Dl]
# (DeepSeek-V3.2's gathered decode buffer has that form) is the one-layer
# stack and takes no layer. `layer` None below is that per-layer case.
_LAYERS = [None, 0, 1, 2]


def _latent_case(kernel, pool, dtype=jnp.bfloat16, L=3):
    """(call(pool, layer=None, mesh=None) -> float32 numpy, ref(pool_l), the
    stacked pool): one kernel of ops/mla_attention.py, interpreted, on fixed
    queries and page tables, and `paged_attention_jnp` in float32 on one
    layer's pool. `pool`: "dense" or "int8" (the dict of "q" and "s")."""
    from dynamo_tpu.models.toolkit import paged_attention_jnp
    from dynamo_tpu.ops import mla_attention as ops

    rng = np.random.default_rng(9)
    H, dc, dr, NP, PS, MP, scale = 4, 32, 16, 24, 4, 5, 0.13
    Dl = dc + dr
    stack = jnp.asarray(rng.standard_normal((L, NP, PS, 1, Dl)), dtype)
    if pool == "int8":
        from dynamo_tpu.models.quant import kv_pool_quantize

        stack = kv_pool_quantize(stack)
    if kernel == "decode":
        B = 3
        q = jnp.asarray(rng.standard_normal((B, H, Dl)), dtype)
        kv = jnp.asarray([3, 9, 20], jnp.int32)
        rows, pos, qg = (kv,), (kv - 1)[:, None], q[:, None, None]
    else:
        B, S = 2, 8
        q = jnp.asarray(rng.standard_normal((B, S, H, Dl)), dtype)
        qs, ql = jnp.asarray([4, 0], jnp.int32), jnp.asarray([8, 5], jnp.int32)
        kv = qs + ql
        rows, qg = (qs, ql, kv), q[:, :, None]
        pos = jnp.where(jnp.arange(S)[None] < ql[:, None],
                        qs[:, None] + jnp.arange(S)[None], -1)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))

    def call(pool, layer=None, mesh=None):
        kw = dict(layer=layer, dc=dc, scale=scale, interpret=True)
        if mesh is not None:
            fn = getattr(ops, f"{kernel}_mla_attention_sharded")
            return np.asarray(fn(q, pool, pt, *rows, mesh, **kw), np.float32)
        fn = getattr(ops, f"{kernel}_mla_attention")
        return np.asarray(fn(q, pool, pt, *rows, **kw), np.float32)

    def ref(pool_l):
        if isinstance(pool_l, dict):
            v_view = {"q": pool_l["q"][..., :dc], "s": pool_l["s"]}
        else:
            pool_l = pool_l.astype(jnp.float32)
            v_view = pool_l[..., :dc]
        out = paged_attention_jnp(qg.astype(jnp.float32), pool_l, v_view, pt,
                                  pos, kv, scale=scale)
        out = np.asarray(out[:, 0, 0] if kernel == "decode" else out[:, :, 0])
        if kernel == "prefill":  # padding rows return 0
            out = np.where(np.asarray(pos)[:, :, None, None] >= 0, out, 0.0)
        return out

    return call, ref, stack


def _layer_of(stack, layer):
    """(the operand, the layer to pass, that layer's pool)."""
    pool_l = jax.tree.map(lambda a: a[layer or 0], stack)
    if layer is None:
        return pool_l, None, pool_l
    return stack, jnp.int32(layer), pool_l


@pytest.mark.parametrize("layer", _LAYERS)
def test_decode_mla_attention_int8_matches_jnp(layer):
    """int8 MLA decode kernel (per-token scale folds into scores AND
    values) vs the jnp dict-pool path on the same quantized pool: the
    layer's pool itself, and the stacked dict read at each of its layers."""
    call, ref, stack = _latent_case("decode", "int8", jnp.float32)
    operand, at, pool_l = _layer_of(stack, layer)
    np.testing.assert_allclose(call(operand, at), ref(pool_l),
                               rtol=2e-5, atol=2e-5)


_LATENT_KERNELS = [("decode", "dense"), ("decode", "int8"), ("prefill", "dense")]


@pytest.mark.parametrize("layer", _LAYERS)
@pytest.mark.parametrize("kernel, pool", _LATENT_KERNELS)
def test_latent_kernels_read_the_stacked_pool_at_a_layer(kernel, pool, layer):
    """bf16 queries and pool (and the int8 dict): the stacked pool read at
    layer 0, a middle layer and L - 1 against `paged_attention_jnp` on
    `pool[l]`, and bit for bit against the per-layer call on `pool[l]`,
    since the operand alone differs; another layer's pool gives another
    answer, so the layer is what was read."""
    call, ref, stack = _latent_case(kernel, pool)
    operand, at, pool_l = _layer_of(stack, layer)
    got = call(operand, at)
    assert np.abs(got).max() > 0
    np.testing.assert_allclose(got, ref(pool_l), rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(got, call(pool_l))
    other = jax.tree.map(lambda a: a[((layer or 0) + 1) % 3], stack)
    assert not np.array_equal(got, call(other))


@pytest.mark.parametrize("kernel, pool", _LATENT_KERNELS)
def test_latent_kernels_refuse_a_pool_whose_rank_and_layer_disagree(kernel, pool):
    call, _, stack = _latent_case(kernel, pool)
    with pytest.raises(ValueError, match="takes no layer"):
        call(jax.tree.map(lambda a: a[0], stack), jnp.int32(0))
    with pytest.raises(ValueError, match="needs its layer"):
        call(stack)


def test_the_flash_latent_prefill_refuses_an_int8_pool():
    call, _, stack = _latent_case("prefill", "int8")
    with pytest.raises(NotImplementedError, match="int8"):
        call(stack, jnp.int32(1))


@pytest.mark.parametrize("layer", _LAYERS)
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_sharded_latent_kernels_read_the_stacked_pool_at_a_layer(kernel, layer):
    """The tensor-parallel wrappers: the pool replicated with its layer
    axis, `layer` through shard_map behind the lengths, a shard's heads
    against it."""
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    call, _, stack = _latent_case(kernel, "dense")
    operand, at, pool_l = _layer_of(stack, layer)
    mesh = make_mesh(MeshConfig(model=2))
    np.testing.assert_array_equal(call(operand, at, mesh), call(pool_l))


def test_mla_int8_kernel_full_layer_matches_jnp(monkeypatch):
    """Full-layer: quantized MLA decode through the kernel path
    (DYN_MLA_INT8_KERNEL=1, interpret) == the jnp dict-pool path."""
    import functools as _ft

    import jax.numpy as jnp

    import dynamo_tpu.ops.mla_attention as mla_ops
    from dynamo_tpu.models import llama

    c = get_config("tiny-mla")
    p = llama.init_params(c, jax.random.PRNGKey(0))
    toks = [5, 9, 2, 7, 1]
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]
    k1, v1 = llama.make_kv_pool(c, 8, 4, kv_quantize="int8")
    out, k1, v1 = llama.forward(
        c, p, jnp.asarray([toks]), jnp.asarray([list(range(5))]),
        k1, v1, pt, jnp.asarray([5]),
    )
    ref, _, _ = llama.forward(
        c, p, jnp.asarray([[8]]), jnp.asarray([[5]]), k1, v1, pt,
        jnp.asarray([6]),
    )
    monkeypatch.setenv("DYN_MLA_INT8_KERNEL", "1")
    orig = mla_ops.decode_mla_attention
    calls = {"n": 0}

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, interpret=True, **kw)

    try:
        mla_ops.decode_mla_attention = counting
        got, _, _ = llama.forward(
            c, p, jnp.asarray([[8]]), jnp.asarray([[5]]), k1, v1, pt,
            jnp.asarray([6]), attn_impl="pallas",
        )
    finally:
        mla_ops.decode_mla_attention = orig
    assert calls["n"] > 0, "int8 kernel path never engaged (gate regressed)"
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=3e-2, atol=3e-2
    )


def test_the_runner_names_the_latent_decode_kernels_routine(monkeypatch, caplog):
    """`runner ready` and /debug/device say what the latent decode kernel
    does with its pages, from the walk's own decision (`decode_step`): the
    tile routine, eight pages a grid step where they tile the page table;
    an int8 latent pool rides the kernel only where it is opted in, its
    pages one a step by their scales; the jnp gather names nothing."""
    import logging

    from dynamo_tpu.engine.model_runner import ModelRunner

    c = get_config("tiny-mla")
    kw = dict(num_pages=8, page_size=4)
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.engine.runner"):
        rep = ModelRunner(c, attn_impl="pallas", **kw).device_report()
    assert (rep["decode_page_routine"], rep["decode_step_pages"]) == ("by_tiles", 8)
    assert "decode_page_routine=by_tiles, decode_step_pages=8" in caplog.text
    rep = ModelRunner(c, attn_impl="pallas", max_pages_per_seq=12, **kw).device_report()
    assert (rep["decode_page_routine"], rep["decode_step_pages"]) == ("by_tiles", 4)
    rep = ModelRunner(c, **kw).device_report()
    assert rep["decode_page_routine"] is rep["decode_step_pages"] is None
    int8 = ModelRunner(c, attn_impl="pallas", kv_quantize="int8", **kw)
    rep = int8.device_report()
    assert rep["decode_page_routine"] is rep["decode_step_pages"] is None
    monkeypatch.setenv("DYN_MLA_INT8_KERNEL", "1")
    rep = int8.device_report()
    assert (rep["decode_page_routine"], rep["decode_step_pages"]) == ("by_heads", 1)
    # a model without latent attention names its routine and no count
    rep = ModelRunner(get_config("tiny"), attn_impl="pallas", **kw).device_report()
    assert (rep["decode_page_routine"], rep["decode_step_pages"]) == ("by_tiles", None)
