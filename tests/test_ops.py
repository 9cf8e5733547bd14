"""Pallas kernel tests (interpret mode on CPU; compiled mode is exercised
on real TPU via bench/worker runs)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import paged_attention_jnp
from dynamo_tpu.ops.flash_prefill import prefill_paged_attention
from dynamo_tpu.ops.paged_attention import decode_paged_attention


@pytest.mark.parametrize("kv_lens", [[5, 17, 32, 1], [32, 32, 32, 32], [1, 1, 1, 1]])
def test_decode_paged_attention_matches_reference(kv_lens):
    rng = np.random.default_rng(0)
    B, Hk, G, D, NP, PS, MP = 4, 2, 4, 64, 16, 8, 4
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray(np.asarray(kv_lens, np.int32))

    out = decode_paged_attention(q, kp, vp, pt, kv, interpret=True)
    ref = paged_attention_jnp(q[:, None], kp, vp, pt, (kv - 1)[:, None], kv)[:, 0]
    d = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    assert d < 3e-2, d


def test_decode_paged_attention_ignores_garbage_pages():
    """Pages past kv_len may point anywhere (even shared page 0); masked."""
    rng = np.random.default_rng(1)
    B, Hk, G, D, NP, PS, MP = 2, 1, 2, 64, 8, 8, 4
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)) * 100, jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)) * 100, jnp.bfloat16)
    pt_a = jnp.asarray(np.array([[1, 0, 0, 0], [2, 0, 0, 0]], np.int32))
    pt_b = jnp.asarray(np.array([[1, 7, 6, 5], [2, 3, 4, 5]], np.int32))
    kv = jnp.asarray(np.array([6, 8], np.int32))  # only first page used
    out_a = decode_paged_attention(q, kp, vp, pt_a, kv, interpret=True)
    out_b = decode_paged_attention(q, kp, vp, pt_b, kv, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_a, np.float32), np.asarray(out_b, np.float32)
    )


@pytest.mark.parametrize(
    "q_start,q_len,kv_extra",
    [
        ([0, 0], [16, 9], [0, 0]),  # fresh prefill, one padded seq
        ([24, 8], [16, 16], [0, 0]),  # chunked prefill (prior context)
        ([0, 40], [16, 16], [0, 3]),  # prior ctx + garbage tail pages
    ],
)
def test_prefill_paged_attention_matches_reference(q_start, q_len, kv_extra):
    rng = np.random.default_rng(2)
    B, S, Hk, G, D, NP, PS, MP = 2, 16, 2, 3, 64, 16, 8, 8
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray(q_start, np.int32)
    ql = np.asarray(q_len, np.int32)
    # kv_extra > 0: kv_len admits tokens past the last query position — the
    # causal mask (not kv_len) must exclude them
    kv = jnp.asarray(qs + ql + np.asarray(kv_extra, np.int32))

    out = prefill_paged_attention(
        q, kp, vp, pt, jnp.asarray(qs), jnp.asarray(ql), kv, q_block=8, interpret=True
    )
    # jnp reference: positions with -1 padding
    pos = np.full((B, S), -1, np.int32)
    for b in range(B):
        pos[b, : ql[b]] = np.arange(qs[b], qs[b] + ql[b])
    ref = paged_attention_jnp(q, kp, vp, pt, jnp.asarray(np.maximum(pos, 0)), kv)
    for b in range(B):
        d = np.abs(
            np.asarray(out[b, : ql[b]], np.float32) - np.asarray(ref[b, : ql[b]], np.float32)
        ).max()
        assert d < 3e-2, (b, d)
        # padding rows are zero
        assert np.all(np.asarray(out[b, ql[b] :], np.float32) == 0.0)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
def test_decode_paged_attention_sharded_matches_reference():
    """TP wrapper: kernel inside shard_map over the model axis (heads
    split) must match the unsharded jnp reference."""
    from dynamo_tpu.ops.paged_attention import decode_paged_attention_sharded
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(3)
    B, Hk, G, D, NP, PS, MP = 4, 4, 2, 64, 16, 8, 4
    mesh = make_mesh(MeshConfig(model=2))
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray(np.array([5, 17, 32, 9], np.int32))

    out = decode_paged_attention_sharded(q, kp, vp, pt, kv, mesh, interpret=True)
    ref = paged_attention_jnp(q[:, None], kp, vp, pt, (kv - 1)[:, None], kv)[:, 0]
    d = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    assert d < 3e-2, d


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
def test_prefill_paged_attention_sharded_matches_reference():
    from dynamo_tpu.ops.flash_prefill import prefill_paged_attention_sharded
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(4)
    B, S, Hk, G, D, NP, PS, MP = 2, 16, 2, 3, 64, 16, 8, 8
    mesh = make_mesh(MeshConfig(model=2))
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray([8, 0], np.int32)
    ql = np.asarray([16, 11], np.int32)
    kv = jnp.asarray(qs + ql)

    out = prefill_paged_attention_sharded(
        q, kp, vp, pt, jnp.asarray(qs), jnp.asarray(ql), kv, mesh,
        q_block=8, interpret=True,
    )
    pos = np.full((B, S), 0, np.int32)
    for b in range(B):
        pos[b, : ql[b]] = np.arange(qs[b], qs[b] + ql[b])
    ref = paged_attention_jnp(q, kp, vp, pt, jnp.asarray(pos), kv)
    for b in range(B):
        d = np.abs(
            np.asarray(out[b, : ql[b]], np.float32) - np.asarray(ref[b, : ql[b]], np.float32)
        ).max()
        assert d < 3e-2, (b, d)


# -- int8 KV pools (models/quant.py KV convention) --------------------------
def _q_pools(kp, vp):
    from dynamo_tpu.models.quant import kv_pool_quantize

    return kv_pool_quantize(kp), kv_pool_quantize(vp)


@pytest.mark.parametrize("kv_lens", [[5, 17, 32, 1], [32, 32, 32, 32]])
def test_decode_paged_attention_int8_kv(kv_lens):
    """Quantized-pool kernel == jnp path on the same quantized pools, and
    both stay within the int8 rounding envelope of the bf16 reference."""
    rng = np.random.default_rng(11)
    B, Hk, G, D, NP, PS, MP = 4, 2, 4, 64, 16, 8, 4
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray(np.asarray(kv_lens, np.int32))
    kq, vq = _q_pools(kp, vp)

    out = decode_paged_attention(q, kq, vq, pt, kv, interpret=True)
    ref_q = paged_attention_jnp(q[:, None], kq, vq, pt, (kv - 1)[:, None], kv)[:, 0]
    d = np.abs(np.asarray(out, np.float32) - np.asarray(ref_q, np.float32)).max()
    assert d < 3e-2, d

    ref = paged_attention_jnp(q[:, None], kp, vp, pt, (kv - 1)[:, None], kv)[:, 0]
    d_bf16 = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    assert d_bf16 < 8e-2, d_bf16


def test_prefill_paged_attention_int8_kv():
    rng = np.random.default_rng(12)
    B, S, Hk, G, D, NP, PS, MP = 2, 16, 2, 3, 64, 16, 8, 8
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray([24, 0], np.int32)
    ql = np.asarray([16, 11], np.int32)
    kv = jnp.asarray(qs + ql)
    kq, vq = _q_pools(kp, vp)

    out = prefill_paged_attention(
        q, kq, vq, pt, jnp.asarray(qs), jnp.asarray(ql), kv, q_block=8,
        interpret=True,
    )
    pos = np.full((B, S), 0, np.int32)
    for b in range(B):
        pos[b, : ql[b]] = np.arange(qs[b], qs[b] + ql[b])
    ref_q = paged_attention_jnp(q, kq, vq, pt, jnp.asarray(pos), kv)
    for b in range(B):
        d = np.abs(
            np.asarray(out[b, : ql[b]], np.float32)
            - np.asarray(ref_q[b, : ql[b]], np.float32)
        ).max()
        assert d < 3e-2, (b, d)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
def test_decode_paged_attention_sharded_int8_kv():
    from dynamo_tpu.ops.paged_attention import decode_paged_attention_sharded
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(13)
    B, Hk, G, D, NP, PS, MP = 4, 2, 4, 64, 40, 8, 8
    mesh = make_mesh(MeshConfig(model=2))
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray(np.array([5, 17, 32, 64], np.int32))
    kq, vq = _q_pools(kp, vp)

    out = decode_paged_attention_sharded(q, kq, vq, pt, kv, mesh, interpret=True)
    ref_q = paged_attention_jnp(q[:, None], kq, vq, pt, (kv - 1)[:, None], kv)[:, 0]
    d = np.abs(np.asarray(out, np.float32) - np.asarray(ref_q, np.float32)).max()
    assert d < 3e-2, d


# -- the decode kernel's page walk (its grid is a work list of live pages) ---
# name -> (Hk, G, D, PS, MP[, Dv]): G 1 with Hk filling bf16's 16-row tiles
# is the by-rows routine (phi-3's MHA at D 96); every other dense pool the
# tile routine, a KV head at a time: ai21-jamba2-3b's 20 query heads on one
# KV head (a step brings 2 pages of a table 6 wide, all 8 of one 8 wide),
# GQA at 2 / 4 / 8 KV heads x 2 / 3 / 16 query heads on each, G 1 at half a
# bf16 tile, values narrower than keys, a table 12 wide (4 pages a step)
_WALK_GEOMS = {
    "mha-d96": (16, 1, 96, 16, 6),
    "gqa-g3": (2, 3, 128, 8, 6),
    "gqa-g4": (2, 4, 128, 8, 6),
    "gemma-ps16": (4, 2, 128, 16, 6),
    "mqa-g20": (1, 20, 128, 8, 6),
    "mqa-g8-d64": (1, 8, 64, 4, 8),
    "mqa-g1": (1, 1, 128, 8, 6),
    "gqa-h4-g16": (4, 16, 128, 8, 6),
    "gqa-h8-g2-mp12": (8, 2, 64, 8, 12),
    "half-tile-g1": (8, 1, 128, 8, 6),
    "two-widths-h2-g3": (2, 3, 128, 8, 6, 64),
    "two-widths-h4-g16": (4, 16, 64, 8, 7, 32),
    "two-widths-mha": (16, 1, 64, 8, 6, 32),
    # Llama-3.2-3B at pages of 64: 4 pages a step whole, 8 a shard of two
    "llama-ps64": (8, 3, 128, 64, 8),
}
# name -> (window or None, softcap, int8 KV, sink, pool dtype)
_BF, _F32 = jnp.bfloat16, jnp.float32
_WALK_VARIANTS = {
    "plain": (None, 0.0, False, False, _BF),
    "window-cuts-pages": ("pages", 0.0, False, False, _BF),
    "window-mid-page": ("mid", 0.0, False, False, _BF),
    "int8": (None, 0.0, True, False, _BF),
    "softcap": (None, 30.0, False, False, _BF),
    "int8-window-softcap": ("mid", 30.0, True, False, _BF),
    "sink": (None, 0.0, False, True, _BF),
    "window-sink-f32": ("mid", 0.0, False, True, _F32),
    "window-zero-is-global": ("zero", 0.0, False, False, _BF),
    "softcap-sink-f32": (None, 30.0, False, True, _F32),
}
# the variants PR 41 added run on the geometries it added too, and on one of
# each older routine
_NEW_GEOMS = ("gqa-h4-g16", "gqa-h8-g2-mp12", "half-tile-g1",
              "two-widths-h2-g3", "two-widths-h4-g16", "two-widths-mha")
_NEW_VARIANTS = ("sink", "window-sink-f32", "window-zero-is-global",
                 "softcap-sink-f32")
_WALK_CASES = [(g, v) for g in _WALK_GEOMS for v in _WALK_VARIANTS
               if v not in _NEW_VARIANTS
               or g in _NEW_GEOMS + ("mha-d96", "gqa-g3", "mqa-g20")]


def _walk_lens(PS, MP):
    """One batch with every edge of the walk: a pad row, one token, whole
    pages, one token into the next page, the whole page table, a pad row
    between live ones."""
    return np.asarray([0, 1, PS * 2, PS * 2 + 1, 0, PS * MP], np.int32)


def _walk_case(geom, variant, seed=21, layers=None):
    """(q, kp, vp, pt, kv, window, kw): kw the static extras and the sink."""
    Hk, G, D, PS, MP, *Dv = _WALK_GEOMS[geom]
    Dv = Dv[0] if Dv else D
    win, softcap, quant, sinked, dtype = _WALK_VARIANTS[variant]
    # "pages": lo lands on a page boundary for the longest row (leading
    # pages dead, the first live page whole); "mid": inside a page;
    # "zero": a traced window of 0, global at run time
    window = {None: None, "pages": PS * 2, "mid": PS * 2 + 3, "zero": 0}[win]
    rng = np.random.default_rng(seed)
    kv = _walk_lens(PS, MP)
    B = len(kv)
    NP = B * MP + 2
    shape = (NP, PS, Hk) if layers is None else (layers, NP, PS, Hk)
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), dtype)
    kp = jnp.asarray(rng.standard_normal(shape + (D,)), dtype)
    vp = jnp.asarray(rng.standard_normal(shape + (Dv,)), dtype)
    pt = rng.permutation(NP - 2)[: B * MP].reshape(B, MP).astype(np.int32)
    if quant:
        kp, vp = _q_pools(kp, vp)
    kw = {"softcap": softcap}
    if sinked:
        kw["sink"] = jnp.asarray(rng.standard_normal((Hk, G)), jnp.float32)
    return q, kp, vp, pt, kv, window, kw


def _walk_ref(q, kp, vp, pt, kv, window, kw):
    return paged_attention_jnp(
        q[:, None], kp, vp, jnp.asarray(pt),
        jnp.asarray(np.maximum(kv - 1, 0))[:, None], jnp.asarray(kv),
        window=None if window is None else jnp.int32(window), **kw,
    )[:, 0]


def _walk_close(out, ref, kv):
    out = np.asarray(out, np.float32)
    assert out.shape == ref.shape
    assert np.all(out[kv == 0] == 0.0)  # a pad row: defined, and zero
    d = np.abs(out - np.asarray(ref, np.float32))[kv > 0].max()
    assert d < 3e-2, d


@pytest.mark.parametrize("geom,variant", _WALK_CASES)
def test_decode_walk_matches_reference(geom, variant):
    q, kp, vp, pt, kv, window, kw = _walk_case(geom, variant)
    out = decode_paged_attention(
        q, kp, vp, jnp.asarray(pt), jnp.asarray(kv),
        None if window is None else jnp.int32(window), interpret=True, **kw,
    )
    _walk_close(out, _walk_ref(q, kp, vp, pt, kv, window, kw), kv)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("geom", ["mha-d96", "gqa-g3", "mqa-g20", "mqa-g8-d64",
                                  "gqa-h4-g16", "two-widths-h2-g3"])
def test_decode_walk_reads_stacked_pool_layer(geom, layer):
    q, kp, vp, pt, kv, window, kw = _walk_case(
        geom, "window-mid-page", layers=3)
    out = decode_paged_attention(
        q, kp, vp, jnp.asarray(pt), jnp.asarray(kv), jnp.int32(window),
        jnp.int32(layer), interpret=True,
    )
    _walk_close(out, _walk_ref(q, kp[layer], vp[layer], pt, kv, window, kw),
                kv)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
@pytest.mark.parametrize("geom,variant", [
    ("gqa-g4", "window-mid-page"), ("gqa-g4", "int8"),
    ("gemma-ps16", "window-mid-page"), ("gemma-ps16", "int8"),
    ("gqa-h4-g16", "window-zero-is-global"), ("gqa-h8-g2-mp12", "softcap"),
    ("two-widths-h4-g16", "window-mid-page"),
    ("two-widths-h2-g3", "window-sink-f32"), ("gqa-h4-g16", "sink"),
    ("llama-ps64", "plain"), ("llama-ps64", "window-mid-page")])
def test_decode_walk_sharded(geom, variant):
    """Heads over two shards, each the tile routine on its local heads:
    gqa-g4's two KV heads leave each shard ONE, on its local 4-d view (int8:
    by heads). llama-ps64: the walk is built outside `shard_map`, where the
    pool's page is 256 KB (4 pages a step), for a shard that sees 128 KB
    (8): the count is the shard's, and the kernel reads it off the walk."""
    from dynamo_tpu.ops.paged_attention import decode_paged_attention_sharded
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    q, kp, vp, pt, kv, window, kw = _walk_case(geom, variant)
    out = decode_paged_attention_sharded(
        q, kp, vp, jnp.asarray(pt), jnp.asarray(kv),
        make_mesh(MeshConfig(model=2)),
        window=None if window is None else jnp.int32(window),
        interpret=True, **kw,
    )
    _walk_close(out, _walk_ref(q, kp, vp, pt, kv, window, kw), kv)


def _pallas_calls(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, found)
    return found


def test_page_routine_follows_heads_and_dtype():
    """The routine is a fact of (Hk, G, pool dtype, a sink, one width or
    two): nothing else picks it, and one place decides."""
    from dynamo_tpu.ops.paged_attention import page_routine, step_tiles

    bf, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
    assert page_routine(32, 1, bf, False, False, True) == "by_rows"
    assert page_routine(8, 1, f32, False, False, True) == "by_rows"
    assert page_routine(8, 1, bf, False, False, True) == "by_tiles"  # half a bf16 tile
    assert page_routine(8, 4, bf, False, False, True) == "by_tiles"
    assert page_routine(1, 20, bf, False, False, True) == "by_tiles"
    assert page_routine(1, 1, f32, False, False, True) == "by_tiles"
    assert page_routine(1, 20, i8, True, False, True) == "by_heads"  # scales a (token, head)
    assert page_routine(32, 1, i8, True, False, True) == "by_heads"
    # a sink, or values narrower than keys: by_rows has neither
    assert page_routine(32, 1, bf, False, True, True) == "by_tiles"
    assert page_routine(32, 1, bf, False, False, False) == "by_tiles"
    assert page_routine(4, 16, bf, False, False, False) == "by_tiles"
    assert page_routine(8, 8, bf, False, True, False) == "by_tiles"
    assert page_routine(8, 8, i8, True, False, False) == "by_heads"
    # a step's pages: a power of two up to 8 that tiles the page table and
    # keeps the step's bytes in bounds. ai21-jamba2-3b's page (32 KB):
    jamba = 2 * 64 * 128 * 2
    assert [step_tiles(jamba, mp) for mp in (64, 256, 12, 6, 7)] == [8, 8, 4, 2, 1]
    # mimo-v2-flash's global and window pages (196,608 and 393,216 B)
    assert step_tiles(64 * 4 * (256 + 128) * 2, 96) == 4
    assert step_tiles(64 * 8 * (256 + 128) * 2, 96) == 2
    assert step_tiles(64 * 8 * (256 + 128) * 2, 7) == 1
    # latent attention: ONE block for keys and values, reckoned once (49 KB
    # at rank 256's 320 -> 384 lanes, 82 KB at rank 512's 640): eight a
    # step under its cells' page tables and the selecting arm's identity
    # table; an int8 latent pool takes its pages one a step, by its scales
    from dynamo_tpu.ops.paged_attention import decode_step, page_bytes

    def latent(dl, dtype=bf):
        return jax.ShapeDtypeStruct((6, 768, 64, 1, dl), dtype)

    assert page_bytes(1, latent(320), None) == 64 * 384 * 2
    assert page_bytes(1, latent(640), None) == 64 * 640 * 2
    assert page_bytes(1, latent(640), latent(640)) == 2 * 64 * 640 * 2
    for dl, mp in ((320, 64), (640, 128), (640, 32), (640, 576)):
        assert decode_step((1, 32), latent(dl), None, mp, False) == ("by_tiles", 8)
    assert decode_step((1, 1), latent(320, f32), None, 64, False) == ("by_tiles", 8)
    int8 = {"q": latent(320, i8), "s": jax.ShapeDtypeStruct((6, 768, 64, 1), f32)}
    assert decode_step((1, 32), int8, None, 64, False) == ("by_heads", 1)
    # and the GQA pools answer as before
    assert decode_step((1, 20), latent(128), latent(128), 64, False) == ("by_tiles", 8)
    assert decode_step((32, 1), jax.ShapeDtypeStruct((2, 8, 64, 32, 96), bf),
                       jax.ShapeDtypeStruct((2, 8, 64, 32, 96), bf), 64,
                       False) == ("by_rows", 1)


@pytest.mark.parametrize("kernel", ["decode", "ragged"])
def test_one_kv_head_operand_is_the_4d_view(kernel):
    """At Hk 1 a dense pool reaches the kernel as [L, NP, PS, D], the bytes
    the step program carries; an int8 pool at Hk 1 and a dense one at Hk 2
    keep the 5-d operand they have always had."""
    from dynamo_tpu.ops.ragged_paged_attention import ragged_paged_attention

    L, NP, PS, G, D = 2, 8, 8, 20, 128

    def pool_operands(Hk, quant):
        pool = jnp.zeros((L, NP, PS, Hk, D), jnp.bfloat16)
        if quant:
            pool = _q_pools(pool, pool)[0]
        if kernel == "decode":
            fn = lambda: decode_paged_attention(  # noqa: E731
                jnp.zeros((4, Hk, G, D), jnp.bfloat16), pool, pool,
                jnp.zeros((4, 4), jnp.int32), jnp.ones((4,), jnp.int32), None,
                jnp.int32(1), interpret=True)
        else:
            from dynamo_tpu.ops.ragged_paged_attention import (
                build_ragged_metadata,
            )

            md = build_ragged_metadata([1, 5], [3, 0], [4, 5], [[1], [2]], 8,
                                       max_pages=4)
            fn = lambda: ragged_paged_attention(  # noqa: E731
                jnp.zeros((8, Hk, G, D), jnp.bfloat16), pool, pool,
                jnp.asarray(md["seg_page_table"]),
                jnp.asarray(md["seg_kv_lens"]), jnp.asarray(md["meta"]), None,
                jnp.int32(1), interpret=True)
        (call,) = _pallas_calls(jax.make_jaxpr(fn)().jaxpr)
        return {v.aval.shape for v in call.invars
                if v.aval.shape[:3] == (L, NP, PS) and v.aval.ndim >= 4}

    assert pool_operands(1, False) == {(L, NP, PS, D)}
    assert pool_operands(1, True) == {(L, NP, PS, 1, D)}
    assert pool_operands(2, False) == {(L, NP, PS, 2, D)}


@pytest.mark.parametrize("geom,variant", [
    (g, v) for g in ("mha-d96", "gqa-g3", "mqa-g20", "mqa-g8-d64")
    for v in ("plain", "window-mid-page")] + [
    ("gqa-h8-g2-mp12", "window-mid-page"), ("two-widths-h4-g16", "sink"),
    ("two-widths-mha", "window-sink-f32")])
def test_decode_walk_reads_only_live_pages(geom, variant):
    """Every pool page outside the rows' live ranges holds NaN, and every
    page-table entry outside a row's live range names an unowned (NaN)
    page: the result does not change, so no such page was read."""
    from dynamo_tpu.ops.paged_attention import decode_work_list

    Hk, G, D, PS, MP, *_ = _WALK_GEOMS[geom]
    q, kp, vp, pt, kv, window, kw = _walk_case(geom, variant)
    win = None if window is None else jnp.int32(window)
    clean = decode_paged_attention(q, kp, vp, jnp.asarray(pt),
                                   jnp.asarray(kv), win, interpret=True, **kw)
    work, n_work = decode_work_list(jnp.asarray(kv), win, PS, MP)
    live = np.zeros(pt.shape, bool)
    live.reshape(-1)[np.asarray(work)[: int(n_work)]] = True
    NP = kp.shape[0]
    dead_pages = np.setdiff1d(np.arange(NP), pt[live])
    assert NP - 1 in dead_pages  # nobody's: what dead entries point at
    poison = jnp.asarray(dead_pages)
    kp_n = kp.at[poison].set(jnp.nan)
    vp_n = vp.at[poison].set(jnp.nan)
    pt_n = np.where(live, pt, NP - 1).astype(np.int32)
    out = decode_paged_attention(q, kp_n, vp_n, jnp.asarray(pt_n),
                                 jnp.asarray(kv), win, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(clean, np.float32))


@pytest.mark.parametrize("window", [None, 40])
def test_decode_walk_steps_follow_live_pages_not_table_width(window):
    """The grid is a traced bound, the count of live pages: the same for
    the same rows under a page table 8 and 64 wide."""
    from dynamo_tpu.ops.paged_attention import decode_work_list

    PS = 16
    kv = np.asarray([0, 1, 16, 17, 100, 128, 0, 33], np.int32)
    win = None if window is None else jnp.int32(window)
    lo = np.maximum(kv - window, 0) if window else np.zeros_like(kv)
    expect = int(np.sum(np.where(kv > 0, (kv - 1) // PS - lo // PS + 1, 0)))
    steps = {}
    for MP in (8, 64):
        work, n_work = decode_work_list(jnp.asarray(kv), win, PS, MP)
        work = np.asarray(work)[: int(n_work)]
        steps[MP] = [(int(e) // MP, int(e) % MP) for e in work]
    assert len(steps[8]) == len(steps[64]) == expect
    assert steps[8] == steps[64]  # the same (row, page) walk
    assert steps[8] == sorted(steps[8])  # rows in order, pages ascending

    def grid_of(MP):
        q = jnp.zeros((len(kv), 2, 2, 64), jnp.bfloat16)
        pool = jnp.zeros((4, PS, 2, 64), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(functools.partial(
            decode_paged_attention, interpret=True))(
            q, pool, pool, jnp.zeros((len(kv), MP), jnp.int32),
            jnp.asarray(kv), win)
        eqns = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                if e.primitive.name == "pallas_call"]
        (gm,) = [e.params["grid_mapping"] for e in eqns]
        return gm.grid, gm.num_dynamic_grid_bounds

    # one dynamic grid dimension, and nothing static of the table's width
    assert grid_of(8) == grid_of(64)
    assert grid_of(8)[1] == 1 and len(grid_of(8)[0]) == 1


@pytest.mark.parametrize("geom", ["mha-d96", "gqa-g3", "mqa-g20", "mqa-g8-d64",
                                  "gqa-h8-g2-mp12", "two-widths-h4-g16"])
def test_decode_walk_stays_inside_the_page_table(geom):
    """A length past MP * PS (nothing the engine sends) walks the table's
    MP pages like a full row: the list never outgrows its W entries and
    the row is still finalized."""
    from dynamo_tpu.ops.paged_attention import decode_work_list

    Hk, G, D, PS, MP, *_ = _WALK_GEOMS[geom]
    q, kp, vp, pt, kv, window, kw = _walk_case(geom, "plain")
    over = np.where(kv == PS * MP, PS * MP + 5, kv).astype(np.int32)
    _, n_full = decode_work_list(jnp.asarray(kv), None, PS, MP)
    _, n_over = decode_work_list(jnp.asarray(over), None, PS, MP)
    assert int(n_over) == int(n_full) <= len(kv) * MP
    out = decode_paged_attention(q, kp, vp, jnp.asarray(pt),
                                 jnp.asarray(over), interpret=True)
    full = decode_paged_attention(q, kp, vp, jnp.asarray(pt),
                                  jnp.asarray(kv), interpret=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(full, np.float32))


# -- MLA decode kernel -------------------------------------------------------
# The latent decode kernel walks `decode_walk`'s list over its one pool, a
# step `step_tiles` pages of the row. name -> (PS, MP, the rows' lengths,
# the pool's dtype, "dense" / "int8" / "identity": the selecting arm's
# gathered buffer under its own identity table): the toy sizes (2 pages a
# step of a table 6 wide); pages of 64 tokens under a table 16 wide (8 a
# step): one token, a context that ends inside a tile, on a page's edge,
# one token into the next page, on a step's edge and one token past it; a
# pad row between live rows; tables whose width makes a step 1, 2, 4 and 8
# pages; the bf16 pool (the MXU takes it as it lies) beside the float32
# one; the int8 routine, one page a step on the same list
_BF16, _F32_ = jnp.bfloat16, jnp.float32
_MLA_CASES = {
    "toy-1-9-24": (4, 6, [1, 9, 24], _F32_, "dense"),
    "toy-4-4-4": (4, 6, [4, 4, 4], _F32_, "dense"),
    "toy-24-1-13": (4, 6, [24, 1, 13], _F32_, "dense"),
    "page64-edges": (64, 16, [1, 63, 64, 65, 8 * 64, 8 * 64 + 1], _F32_, "dense"),
    "page64-edges-bf16": (64, 16, [1, 63, 64, 65, 8 * 64, 8 * 64 + 1], _BF16, "dense"),
    "pad-row-between": (4, 6, [9, 0, 24, 0], _F32_, "dense"),
    "pad-rows-bf16": (4, 8, [0, 32, 0, 5], _BF16, "dense"),
    "tiles-1": (4, 7, [28, 1, 13], _F32_, "dense"),
    "tiles-2": (4, 6, [24, 5, 13], _F32_, "dense"),
    "tiles-4": (4, 12, [48, 16, 17], _F32_, "dense"),
    "tiles-8": (4, 8, [32, 1, 13], _F32_, "dense"),
    "tiles-8-two-steps-bf16": (4, 16, [64, 33, 32, 0], _BF16, "dense"),
    "whole-table-bf16": (4, 6, [24, 24, 24], _BF16, "dense"),
    "int8": (4, 6, [1, 9, 24], _F32_, "int8"),
    "int8-pad-row": (4, 8, [32, 0, 13], _F32_, "int8"),
    "int8-page64": (64, 16, [65, 8 * 64 + 1, 1], _F32_, "int8"),
    "identity-table": (4, 8, [32, 7, 0, 20], _F32_, "identity"),
    "identity-table-bf16": (64, 4, [256, 100, 3], _BF16, "identity"),
}
_MLA_TILES = {"tiles-1": 1, "tiles-2": 2, "tiles-4": 4, "tiles-8": 8,
              "page64-edges": 8, "toy-1-9-24": 2, "int8": 1,
              "identity-table": 8, "identity-table-bf16": 4}


def _mla_setup(B=3, H=4, dc=32, dr=16, NP=32, PS=4, MP=6, seed=3,
               dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    Dl = dc + dr
    q = jnp.asarray(rng.standard_normal((B, H, Dl)), dtype)
    lat = jnp.asarray(rng.standard_normal((NP, PS, 1, Dl)), dtype)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    return q, lat, pt


def _mla_case(case, dc=32, dr=16):
    """(q, the pool operand, the float32 / int8 pool the reference reads,
    page table, lengths)."""
    PS, MP, kv_lens, dtype, kind = _MLA_CASES[case]
    B = len(kv_lens)
    q, lat, pt = _mla_setup(B=B, dc=dc, dr=dr, NP=B * MP + 2, PS=PS, MP=MP,
                            dtype=dtype)
    # (the pool's last two pages are nobody's: what dead entries point at)
    pt = jnp.asarray(np.random.default_rng(4).permutation(B * MP)
                     .reshape(B, MP).astype(np.int32))
    if kind == "identity":  # a row's buffer is its own pages, in order
        pt = jnp.arange(B * MP, dtype=jnp.int32).reshape(B, MP)
    pool = lat
    if kind == "int8":
        from dynamo_tpu.models.quant import kv_pool_quantize

        pool = kv_pool_quantize(lat)
    return q, pool, pt, jnp.asarray(kv_lens, jnp.int32)


def _mla_ref(q, pool, pt, kv, dc, scale):
    if isinstance(pool, dict):
        v_view = {"q": pool["q"][..., :dc], "s": pool["s"]}
    else:
        pool = pool.astype(jnp.float32)
        v_view = pool[..., :dc]
    qg = q.astype(jnp.float32)[:, None, None]  # [B, 1, 1, H, Dl]
    return np.asarray(paged_attention_jnp(
        qg, pool, v_view, pt, jnp.maximum(kv - 1, 0)[:, None], kv,
        scale=scale)[:, 0, 0])  # [B, H, dc]


@pytest.mark.parametrize("case", list(_MLA_CASES))
def test_decode_mla_attention_matches_reference(case):
    from dynamo_tpu.ops.mla_attention import decode_mla_attention, latent_walk

    dc, dr = 32, 16
    q, pool, pt, kv = _mla_case(case, dc, dr)
    scale = (24 + dr) ** -0.5  # distinct from Dl**-0.5: must be honored
    if case in _MLA_TILES:
        assert latent_walk(q.shape[1], pool, pt, kv).tiles == _MLA_TILES[case]
    out = np.asarray(decode_mla_attention(q, pool, pt, kv, dc=dc, scale=scale,
                                          interpret=True), np.float32)
    ref = _mla_ref(q, pool, pt, kv, dc, scale)
    live = np.asarray(kv) > 0
    assert np.all(out[~live] == 0.0)  # a pad row: defined, and zero
    tol = 2e-5 if q.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)


@pytest.mark.parametrize("case", ["toy-1-9-24", "tiles-1", "tiles-4", "tiles-8",
                                  "tiles-8-two-steps-bf16", "pad-row-between",
                                  "page64-edges-bf16", "int8-pad-row"])
def test_decode_mla_attention_ignores_garbage_pages(case):
    """Every page outside the rows' live runs holds NaN (int8: its scale),
    and every dead page-table entry names such a page: the bits do not
    change, so neither a dead entry nor a dead page was read."""
    from dynamo_tpu.ops.mla_attention import decode_mla_attention

    dc = 32
    q, pool, pt, kv = _mla_case(case, dc)
    PS = jax.tree.leaves(pool)[0].shape[1]
    pt, n_pages = np.asarray(pt), -(-np.asarray(kv) // PS)
    live = np.arange(pt.shape[1])[None, :] < n_pages[:, None]
    NP = jax.tree.leaves(pool)[0].shape[0]
    dead_pages = jnp.asarray(np.setdiff1d(np.arange(NP), pt[live]))
    assert NP - 1 in np.asarray(dead_pages)  # nobody's
    if isinstance(pool, dict):
        bad = {"q": pool["q"], "s": pool["s"].at[dead_pages].set(jnp.nan)}
    else:
        bad = pool.at[dead_pages].set(jnp.nan)
    pt_bad = jnp.asarray(np.where(live, pt, NP - 1).astype(np.int32))
    out_a = decode_mla_attention(q, pool, jnp.asarray(pt), kv, dc=dc,
                                 scale=0.1, interpret=True)
    out_b = decode_mla_attention(q, bad, pt_bad, kv, dc=dc, scale=0.1,
                                 interpret=True)
    assert np.asarray(out_a, np.float32)[np.asarray(kv) > 0].any()
    np.testing.assert_array_equal(np.asarray(out_a, np.float32),
                                  np.asarray(out_b, np.float32))


@pytest.mark.parametrize("walk", ["built-in-the-wrapper", "handed-in"])
@pytest.mark.parametrize("case", ["toy-1-9-24", "pad-row-between", "tiles-8",
                                  "tiles-8-two-steps-bf16"])
def test_decode_mla_attention_sharded_matches_reference(case, walk):
    """Heads over two shards against the replicated pool; the walk is
    built once outside `shard_map`, by the wrapper or by its caller (a
    model's forward, above its layer scan), and rides in replicated."""
    from dynamo_tpu.ops.mla_attention import (
        decode_mla_attention,
        decode_mla_attention_sharded,
        latent_walk,
    )
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    dc = 32
    q, pool, pt, kv = _mla_case(case, dc)
    mesh = make_mesh(MeshConfig(model=2))
    work = None
    if walk == "handed-in":
        work = latent_walk(q.shape[1] // 2, pool, pt, kv)
    out = decode_mla_attention_sharded(
        q, pool, pt, kv, mesh, work=work, dc=dc, scale=0.12, interpret=True
    )
    ref = decode_mla_attention(q, pool, pt, kv, dc=dc, scale=0.12,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def test_decode_mla_attention_grid_is_the_live_steps():
    """One traced grid bound, the rows' live steps, whatever the page
    table's width: 64 x 64 tokens under a table 64 wide is 8 steps of 8
    pages, not 64; and the page block appears once a tile on one operand."""
    from dynamo_tpu.ops.mla_attention import decode_mla_attention, latent_walk

    PS, MP, H, Dl, dc = 64, 64, 4, 48, 32
    kv = jnp.asarray([0, 1, 64 * 5, 64 * 8, 64 * 8 + 1, 64 * 64], jnp.int32)
    pool = jnp.zeros((2, 8, PS, 1, Dl), jnp.bfloat16)
    pt = jnp.zeros((len(kv), MP), jnp.int32)
    work = latent_walk(H, pool, pt, kv)
    assert (work.routine, work.tiles) == ("by_tiles", 8)
    assert int(work.n_work) == 0 + 1 + 1 + 1 + 2 + 8
    jaxpr = jax.make_jaxpr(functools.partial(
        decode_mla_attention, dc=dc, scale=0.1, interpret=True))(
        jnp.zeros((len(kv), H, Dl), jnp.bfloat16), pool, pt, kv, jnp.int32(1))
    (call,) = _pallas_calls(jaxpr.jaxpr)
    gm = call.params["grid_mapping"]
    assert len(gm.grid) == 1 and gm.num_dynamic_grid_bounds == 1
    blocks = [v.aval.shape for v in call.invars
              if v.aval.shape == (2, 8, PS, Dl)]
    assert len(blocks) == 8  # the 4-d view, a block a tile


def test_mla_forward_pallas_decode_matches_jnp():
    """Full-layer check: forward with attn_impl='pallas' (interpret via
    CPU is not available for compiled mode, so drive _mla_attention's
    kernel path through decode directly)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config

    c = get_config("tiny-mla")
    p = llama.init_params(c, jax.random.PRNGKey(0))
    toks = [5, 9, 2, 7, 1]
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]
    k1, v1 = llama.make_kv_pool(c, 8, 4)
    out, k1, v1 = llama.forward(
        c, p, jnp.asarray([toks]), jnp.asarray([list(range(5))]),
        k1, v1, pt, jnp.asarray([5]),
    )
    # decode step via the jnp path vs the kernel path (interpret mode)
    import dynamo_tpu.ops.mla_attention as mla_ops

    orig = mla_ops.decode_mla_attention
    ref, _, _ = llama.forward(
        c, p, jnp.asarray([[8]]), jnp.asarray([[5]]), k1, v1, pt,
        jnp.asarray([6]),
    )
    try:
        mla_ops.decode_mla_attention = functools.partial(orig, interpret=True)
        got, _, _ = llama.forward(
            c, p, jnp.asarray([[8]]), jnp.asarray([[5]]), k1, v1, pt,
            jnp.asarray([6]), attn_impl="pallas",
        )
    finally:
        mla_ops.decode_mla_attention = orig
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=3e-2, atol=3e-2
    )


# -- batched page copy / permute kernels -------------------------------------


def test_gather_pages_token_and_head_major():
    from dynamo_tpu.ops.block_copy import gather_pages

    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((12, 4, 2, 8)), jnp.float32)
    idx = jnp.asarray([7, 0, 3], jnp.int32)
    out = gather_pages(pool, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pool)[[7, 0, 3]])
    # head-major permute fused into the copy (ref tensor_kernels.cu role)
    hm = gather_pages(pool, idx, head_major=True, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(hm), np.asarray(pool)[[7, 0, 3]].transpose(0, 2, 1, 3)
    )


def test_scatter_pages_in_place():
    from dynamo_tpu.ops.block_copy import scatter_pages

    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal((10, 4, 2, 8)), jnp.float32)
    before = np.asarray(pool).copy()
    pages = jnp.asarray(rng.standard_normal((2, 4, 2, 8)), jnp.float32)
    out = scatter_pages(pool, jnp.asarray([5, 1], jnp.int32), pages,
                        interpret=True)
    got = np.asarray(out)
    np.testing.assert_array_equal(got[5], np.asarray(pages)[0])
    np.testing.assert_array_equal(got[1], np.asarray(pages)[1])
    # untouched pages survive the aliased write
    for p in (0, 2, 3, 4, 6, 7, 8, 9):
        np.testing.assert_array_equal(got[p], before[p])


def test_gather_scatter_roundtrip_transfer():
    """The transfer pattern: export pages from pool A, import into
    different slots of pool B."""
    from dynamo_tpu.ops.block_copy import gather_pages, scatter_pages

    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((8, 4, 2, 8)), jnp.float32)
    b = jnp.zeros((8, 4, 2, 8), jnp.float32)
    wire = gather_pages(a, jnp.asarray([2, 6], jnp.int32), interpret=True)
    b2 = scatter_pages(b, jnp.asarray([0, 4], jnp.int32), wire, interpret=True)
    np.testing.assert_array_equal(np.asarray(b2)[0], np.asarray(a)[2])
    np.testing.assert_array_equal(np.asarray(b2)[4], np.asarray(a)[6])


def test_runner_transfer_via_copy_kernels(monkeypatch):
    """DYN_KV_COPY_KERNEL=1 routes export/import page movement through
    the Pallas batched-copy kernels; the wire roundtrip must be
    bit-identical to the default XLA gather/scatter path."""
    monkeypatch.setenv("DYN_KV_COPY_KERNEL", "1")
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config

    def mk():
        return ModelRunner(
            get_config("tiny"), num_pages=16, page_size=4,
            max_pages_per_seq=8, decode_buckets=(1, 2),
            prefill_buckets=(8,), seed=5,
        )

    r = mk()
    assert r._kv_copy_kernel
    r.prefill([3, 1, 4, 1, 5, 9, 2, 6], 0, [0, 1], prior_len=0)
    payload = r.export_pages([0, 1])
    monkeypatch.delenv("DYN_KV_COPY_KERNEL")
    ref = mk()
    assert not ref._kv_copy_kernel
    ref.prefill([3, 1, 4, 1, 5, 9, 2, 6], 0, [0, 1], prior_len=0)
    ref_payload = ref.export_pages([0, 1])
    assert payload["k"] == ref_payload["k"] and payload["v"] == ref_payload["v"]

    monkeypatch.setenv("DYN_KV_COPY_KERNEL", "1")
    r2 = mk()
    r2.import_pages([5, 9], 0, payload)
    got = np.asarray(r2.k_pool[:, [5, 9]])
    np.testing.assert_array_equal(got, np.asarray(ref.k_pool[:, [0, 1]]))


@pytest.mark.parametrize(
    "q_start,q_len,kv_extra",
    [([0, 0], [8, 5], [0, 0]),        # fresh prefill, one padded seq
     ([12, 4], [8, 8], [0, 0]),       # chunked prefill (prior context)
     ([0, 16], [8, 8], [0, 3])],      # prior ctx + kv past the chunk
)
def test_prefill_mla_attention_matches_reference(q_start, q_len, kv_extra):
    from dynamo_tpu.models.llama import paged_attention_jnp
    from dynamo_tpu.ops.mla_attention import prefill_mla_attention

    rng = np.random.default_rng(7)
    B, S, H, dc, dr, NP, PS, MP = 2, 8, 4, 32, 16, 32, 4, 8
    Dl = dc + dr
    q = jnp.asarray(rng.standard_normal((B, S, H, Dl)), jnp.float32)
    lat = jnp.asarray(rng.standard_normal((NP, PS, 1, Dl)), jnp.float32)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray(q_start, np.int32)
    ql = np.asarray(q_len, np.int32)
    kv = jnp.asarray(qs + ql + np.asarray(kv_extra, np.int32))
    scale = 0.13

    out = prefill_mla_attention(
        q, lat, pt, jnp.asarray(qs), jnp.asarray(ql), kv,
        dc=dc, scale=scale, q_block=4, interpret=True,
    )
    pos = np.full((B, S), 0, np.int32)
    for b in range(B):
        pos[b, : ql[b]] = np.arange(qs[b], qs[b] + ql[b])
    qg = q[:, :, None, :, :]  # [B, S, 1, H, Dl]
    ref = paged_attention_jnp(
        qg, lat, lat[..., :dc], pt, jnp.asarray(pos), kv, scale=scale
    )[:, :, 0]  # [B, S, H, dc]
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out[b, : ql[b]]), np.asarray(ref[b, : ql[b]]),
            rtol=2e-5, atol=2e-5,
        )
        assert np.all(np.asarray(out[b, ql[b]:]) == 0.0)


def test_mla_forward_pallas_prefill_matches_jnp():
    """Full-layer: prefill via the flash MLA kernel (interpret) == jnp."""
    import functools as _ft

    import dynamo_tpu.ops.mla_attention as mla_ops
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config

    c = get_config("tiny-mla")
    p = llama.init_params(c, jax.random.PRNGKey(4))
    toks = [5, 9, 2, 7, 1, 8, 3, 4]
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]
    k1, v1 = llama.make_kv_pool(c, 8, 4)
    ref, _, _ = llama.forward(
        c, p, jnp.asarray([toks]), jnp.asarray([list(range(8))]),
        k1, v1, pt, jnp.asarray([8]),
    )
    orig = mla_ops.prefill_mla_attention
    try:
        mla_ops.prefill_mla_attention = _ft.partial(orig, interpret=True)
        k2, v2 = llama.make_kv_pool(c, 8, 4)
        got, _, _ = llama.forward(
            c, p, jnp.asarray([toks]), jnp.asarray([list(range(8))]),
            k2, v2, pt, jnp.asarray([8]), attn_impl="pallas",
        )
    finally:
        mla_ops.prefill_mla_attention = orig
    # bf16 online-softmax vs dense-softmax accumulate differently over
    # the layer stack (f32 unit parity above is 2e-5); tolerance covers
    # the bf16 envelope across 2 layers
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=7e-2, atol=7e-2
    )


def test_block_copy_kernel_tp2_mesh(monkeypatch):
    """VERDICT r4 #8: the Pallas copy/permute kernels run under shard_map
    on a TP=2 head-sharded pool — export/import through the kernel path
    must be byte-identical to the XLA gather/scatter path."""
    import numpy as np

    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.parallel.mesh import MeshConfig

    def build(kernel_on):
        if kernel_on:
            monkeypatch.setenv("DYN_KV_COPY_KERNEL", "1")
        else:
            monkeypatch.delenv("DYN_KV_COPY_KERNEL", raising=False)
        r = ModelRunner(
            get_config("tiny"), MeshConfig(model=2), num_pages=16,
            page_size=4, max_pages_per_seq=8, decode_buckets=(1,),
            prefill_buckets=(8,), seed=3,
        )
        r.prefill([5, 4, 3, 2, 1, 6, 7, 2], 0, [0, 1, 2], prior_len=0)
        return r

    r_kernel = build(True)
    assert r_kernel._kv_copy_kernel and r_kernel._kv_copy_sharded
    r_xla = build(False)
    assert not r_xla._kv_copy_kernel

    pk = r_kernel.export_pages([0, 1])
    px = r_xla.export_pages([0, 1])
    assert pk["k"] == px["k"] and pk["v"] == px["v"]

    # import through the kernel scatter into fresh slots, re-export
    r_kernel.import_pages([8, 9], 0, pk)
    back = r_kernel.export_pages([8, 9])
    assert back["k"] == pk["k"] and back["v"] == pk["v"]


def test_prefill_mla_attention_sharded_matches_reference():
    """TP wrapper for the flash MLA PREFILL kernel (VERDICT r4: the TP
    chunk path used to fall back to the jnp gather): per-head shards
    against the replicated latent pool must reproduce the unsharded
    kernel exactly."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.mla_attention import (
        prefill_mla_attention,
        prefill_mla_attention_sharded,
    )
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    dc, Dl, H, B, S, PS, NP = 32, 48, 4, 2, 8, 4, 16
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, S, H, Dl), jnp.float32)
    lat = jax.random.normal(jax.random.PRNGKey(4), (NP, PS, 1, Dl),
                            jnp.float32)
    pt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    q_start = jnp.asarray([4, 0], jnp.int32)
    q_len = jnp.asarray([8, 5], jnp.int32)
    kv = jnp.asarray([12, 5], jnp.int32)
    mesh = make_mesh(MeshConfig(model=2))
    out = prefill_mla_attention_sharded(
        q, lat, pt, q_start, q_len, kv, mesh, dc=dc, scale=0.11,
        interpret=True,
    )
    ref = prefill_mla_attention(
        q, lat, pt, q_start, q_len, kv, dc=dc, scale=0.11, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# -- Gemma-2 decode on the Pallas kernel (softcap / window / scale) ----------


def _gemma_decode_setup(B=3, Hk=2, G=2, D=16, PS=4, NP=24, MP=5):
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (B, Hk, G, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(8), (NP, PS, Hk, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(9), (NP, PS, Hk, D), jnp.float32)
    pt = jnp.asarray(
        [[0, 1, 2, 3, 4], [5, 6, 7, 8, 9], [10, 11, 12, 13, 14]], jnp.int32
    )
    kv = jnp.asarray([17, 6, 20], jnp.int32)
    return q, k, v, pt, kv


def _jnp_decode_ref(q, k, v, pt, kv, *, scale=None, softcap=0.0, window=None):
    from dynamo_tpu.models.toolkit import paged_attention_jnp

    B = q.shape[0]
    pos = (kv - 1)[:, None]  # decode query position per sequence
    win = None if window is None else jnp.asarray(window)
    out = paged_attention_jnp(
        q[:, None], k, v, pt, pos, kv, scale=scale, softcap=softcap,
        window=win,
    )
    return out[:, 0]


@pytest.mark.parametrize("softcap,window,scale", [
    (50.0, None, None),          # softcap only
    (0.0, 7, None),              # sliding window only
    (30.0, 9, 0.35 ** -0.5),     # the full Gemma-2 combination
    (0.0, 0, None),              # window operand present but 0 = global
])
def test_decode_kernel_gemma_variants_match_jnp(softcap, window, scale):
    from dynamo_tpu.ops.paged_attention import decode_paged_attention

    q, k, v, pt, kv = _gemma_decode_setup()
    win = None if window is None else jnp.int32(window)
    out = decode_paged_attention(
        q, k, v, pt, kv, win, scale=scale, softcap=softcap, interpret=True
    )
    ref = _jnp_decode_ref(q, k, v, pt, kv, scale=scale, softcap=softcap,
                          window=window if window else None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_kernel_gemma_sharded_matches_jnp():
    from dynamo_tpu.ops.paged_attention import decode_paged_attention_sharded
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    q, k, v, pt, kv = _gemma_decode_setup()
    mesh = make_mesh(MeshConfig(model=2))
    out = decode_paged_attention_sharded(
        q, k, v, pt, kv, mesh, window=jnp.int32(7), softcap=25.0,
        interpret=True,
    )
    ref = _jnp_decode_ref(q, k, v, pt, kv, softcap=25.0, window=7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gemma_forward_pallas_decode_matches_jnp():
    """Full-layer: a Gemma-2-shaped config decodes via the Pallas kernel
    (interpret) with per-layer window alternation == the jnp path."""
    import functools as _ft

    import dynamo_tpu.ops.paged_attention as pa_ops
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config

    c = get_config("tiny-gemma2") if _has_config("tiny-gemma2") else None
    if c is None:
        c = get_config("tiny").with_(
            attn_logit_softcap=30.0, sliding_window=8,
            query_pre_attn_scalar=16.0, post_norms=True,
            norm_zero_centered=True, embed_scale=True,
            final_logit_softcap=15.0, act="gelu_tanh",
        )
    p = llama.init_params(c, jax.random.PRNGKey(2))
    toks = [5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9, 1]
    pt = jnp.arange(8, dtype=jnp.int32)[None, :]
    k1, v1 = llama.make_kv_pool(c, 8, 4)
    out, k1, v1 = llama.forward(
        c, p, jnp.asarray([toks]), jnp.asarray([list(range(len(toks)))]),
        k1, v1, pt, jnp.asarray([len(toks)]),
    )
    ref, _, _ = llama.forward(
        c, p, jnp.asarray([[8]]), jnp.asarray([[len(toks)]]), k1, v1, pt,
        jnp.asarray([len(toks) + 1]),
    )
    orig = pa_ops.decode_paged_attention
    try:
        pa_ops.decode_paged_attention = _ft.partial(orig, interpret=True)
        got, _, _ = llama.forward(
            c, p, jnp.asarray([[8]]), jnp.asarray([[len(toks)]]), k1, v1,
            pt, jnp.asarray([len(toks) + 1]), attn_impl="pallas",
        )
    finally:
        pa_ops.decode_paged_attention = orig
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=3e-2, atol=3e-2
    )


def _has_config(name):
    from dynamo_tpu.models.config import get_config

    try:
        get_config(name)
        return True
    except (KeyError, ValueError):
        return False


def test_decode_kernel_int8_window_softcap_matches_jnp():
    """The quantized+windowed kernel variant (_decode_kernel_int8_win)
    has the most hand-maintained arg plumbing (pt, kl, win, q, k, ks, v,
    vs) — pin it against the jnp path on the SAME quantized pools."""
    rng = np.random.default_rng(13)
    B, Hk, G, D, NP, PS, MP = 3, 2, 4, 64, 16, 8, 4
    q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray([9, 25, 31], jnp.int32)
    kq, vq = _q_pools(kp, vp)
    out = decode_paged_attention(
        q, kq, vq, pt, kv, jnp.int32(11), softcap=20.0, interpret=True
    )
    ref = paged_attention_jnp(
        q[:, None], kq, vq, pt, (kv - 1)[:, None], kv,
        softcap=20.0, window=jnp.int32(11),
    )[:, 0]
    d = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32)).max()
    assert d < 3e-2, d


@pytest.mark.parametrize("softcap,window,scale", [
    (40.0, None, None),
    (0.0, 5, None),
    (25.0, 9, 0.5 ** -0.5),
    (0.0, 0, None),  # window operand present but 0 = global at runtime
])
def test_prefill_kernel_gemma_variants_match_jnp(softcap, window, scale):
    """Gemma extras in the FLASH PREFILL kernel: per-row sliding window,
    softcap, scale — against the jnp path, with prior context (q_start>0)
    so the window reaches back across page boundaries."""
    rng = np.random.default_rng(21)
    B, S, Hk, G, D, NP, PS, MP = 2, 16, 2, 3, 64, 16, 8, 8
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray([11, 0], np.int32)
    ql = np.asarray([16, 13], np.int32)
    kv = jnp.asarray(qs + ql)
    win = None if window is None else jnp.int32(window)
    out = prefill_paged_attention(
        q, kp, vp, pt, jnp.asarray(qs), jnp.asarray(ql), kv, win,
        q_block=8, scale=scale, softcap=softcap, interpret=True,
    )
    pos = np.full((B, S), -1, np.int32)
    for b in range(B):
        pos[b, : ql[b]] = np.arange(qs[b], qs[b] + ql[b])
    jwin = None if not window else jnp.int32(window)
    ref = paged_attention_jnp(
        q, kp, vp, pt, jnp.asarray(np.maximum(pos, 0)), kv,
        scale=scale, softcap=softcap, window=jwin,
    )
    # the >1 scale amplifies bf16 input rounding (kernel vs jnp differ in
    # f32 reduction order); the same combo in f32 agrees to 4e-6
    tol = 3e-2 if not (scale and scale > 1) else 6e-2
    for b in range(B):
        d = np.abs(
            np.asarray(out[b, : ql[b]], np.float32)
            - np.asarray(ref[b, : ql[b]], np.float32)
        ).max()
        assert d < tol, (b, d)


def test_prefill_kernel_int8_window_matches_jnp():
    rng = np.random.default_rng(22)
    B, S, Hk, G, D, NP, PS, MP = 2, 8, 2, 3, 64, 16, 8, 8
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = np.asarray([9, 0], np.int32)
    ql = np.asarray([8, 6], np.int32)
    kv = jnp.asarray(qs + ql)
    kq, vq = _q_pools(kp, vp)
    out = prefill_paged_attention(
        q, kq, vq, pt, jnp.asarray(qs), jnp.asarray(ql), kv, jnp.int32(6),
        q_block=8, softcap=15.0, interpret=True,
    )
    pos = np.full((B, S), -1, np.int32)
    for b in range(B):
        pos[b, : ql[b]] = np.arange(qs[b], qs[b] + ql[b])
    ref = paged_attention_jnp(
        q, kq, vq, pt, jnp.asarray(np.maximum(pos, 0)), kv,
        softcap=15.0, window=jnp.int32(6),
    )
    for b in range(B):
        d = np.abs(
            np.asarray(out[b, : ql[b]], np.float32)
            - np.asarray(ref[b, : ql[b]], np.float32)
        ).max()
        assert d < 3e-2, (b, d)


def test_prefill_kernel_gemma_sharded_matches_jnp():
    from dynamo_tpu.ops.flash_prefill import prefill_paged_attention_sharded
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(23)
    B, S, Hk, G, D, NP, PS, MP = 2, 8, 2, 3, 64, 16, 8, 8
    q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    qs = jnp.asarray([3, 0], jnp.int32)
    ql = jnp.asarray([8, 8], jnp.int32)
    kv = qs + ql
    mesh = make_mesh(MeshConfig(model=2))
    out = prefill_paged_attention_sharded(
        q, kp, vp, pt, qs, ql, kv, mesh, window=jnp.int32(5), softcap=20.0,
        q_block=8, interpret=True,
    )
    pos = jnp.stack([jnp.arange(3, 11), jnp.arange(0, 8)])
    ref = paged_attention_jnp(
        q, kp, vp, pt, pos, kv, softcap=20.0, window=jnp.int32(5),
    )
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


# -- ragged paged attention (ops/ragged_paged_attention.py) -----------------
def _ragged_case(seed, Tb=32, Hk=2, G=3, D=64, NP=48, PS=8, MP=8):
    """Mixed dispatch shapes: two decode rows (q_len=1) + a fresh prefill
    chunk + a chunked prefill with prior context, disjoint pages, flat
    token axis padded to the Tb bucket."""
    from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

    rng = np.random.default_rng(seed)
    q_lens = [1, 1, 9, 16]
    q_starts = [11, 0, 0, 8]
    kv_lens = [12, 1, 9, 24]
    perm = rng.permutation(NP)
    rows = [perm[i * MP : (i + 1) * MP].astype(np.int32).tolist()
            for i in range(len(q_lens))]
    q = jnp.asarray(rng.standard_normal((Tb, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, PS, Hk, D)), jnp.bfloat16)
    md = build_ragged_metadata(q_lens, q_starts, kv_lens, rows, Tb,
                               max_pages=MP)
    return q, kp, vp, md, (q_lens, q_starts, kv_lens, rows)


@pytest.mark.parametrize(
    "softcap,window",
    [(0.0, None), (30.0, None), (0.0, 16), (30.0, 16)],
)
def test_ragged_paged_attention_matches_reference(softcap, window):
    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_attention_reference, ragged_paged_attention,
    )

    q, kp, vp, md, (q_lens, *_rest) = _ragged_case(20)
    win = jnp.int32(window) if window is not None else None
    out = ragged_paged_attention(
        q, kp, vp, jnp.asarray(md["seg_page_table"]),
        jnp.asarray(md["seg_kv_lens"]), jnp.asarray(md["meta"]), win,
        softcap=softcap, interpret=True,
    )
    ref = ragged_attention_reference(
        q, kp, vp, jnp.asarray(md["tok_page_table"]),
        jnp.asarray(md["tok_positions"]), jnp.asarray(md["tok_kv_lens"]),
        softcap=softcap, window=win,
    )
    T = int(sum(q_lens))
    d = np.abs(np.asarray(out[:T], np.float32)
               - np.asarray(ref[:T], np.float32)).max()
    assert d < 3e-2, d
    # bucket-padding rows (covered by the dummy tail segment) are zero
    assert np.all(np.asarray(out[T:], np.float32) == 0.0)


def test_ragged_paged_attention_matches_subsumed_kernels():
    """Parity with the two kernels it replaces: each decode segment ==
    decode_paged_attention, each chunk segment == prefill_paged_attention
    on the same pools/pages."""
    from dynamo_tpu.ops.ragged_paged_attention import ragged_paged_attention

    q, kp, vp, md, (q_lens, q_starts, kv_lens, rows) = _ragged_case(21)
    out = ragged_paged_attention(
        q, kp, vp, jnp.asarray(md["seg_page_table"]),
        jnp.asarray(md["seg_kv_lens"]), jnp.asarray(md["meta"]),
        interpret=True,
    )
    cu = md["cu_q_lens"]
    for s, ql in enumerate(q_lens):
        pt1 = jnp.asarray(np.asarray(rows[s], np.int32)[None])
        kv1 = jnp.asarray([kv_lens[s]], jnp.int32)
        lo = int(cu[s])
        if ql == 1:
            ref = decode_paged_attention(q[lo][None], kp, vp, pt1, kv1,
                                         interpret=True)[0]
            seg = out[lo]
        else:
            S = 16
            qb = jnp.zeros((1, S) + q.shape[1:], q.dtype)
            qb = qb.at[0, :ql].set(q[lo : lo + ql])
            ref = prefill_paged_attention(
                qb, kp, vp, pt1, jnp.asarray([q_starts[s]], jnp.int32),
                jnp.asarray([ql], jnp.int32), kv1, q_block=8, interpret=True,
            )[0, :ql]
            seg = out[lo : lo + ql]
        d = np.abs(np.asarray(seg, np.float32)
                   - np.asarray(ref, np.float32)).max()
        assert d < 3e-2, (s, d)


@pytest.mark.parametrize("window", [None, 16])
def test_ragged_paged_attention_int8_kv(window):
    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_attention_reference, ragged_paged_attention,
    )

    q, kp, vp, md, (q_lens, *_rest) = _ragged_case(22)
    kq, vq = _q_pools(kp, vp)
    win = jnp.int32(window) if window is not None else None
    out = ragged_paged_attention(
        q, kq, vq, jnp.asarray(md["seg_page_table"]),
        jnp.asarray(md["seg_kv_lens"]), jnp.asarray(md["meta"]), win,
        interpret=True,
    )
    ref_q = ragged_attention_reference(
        q, kq, vq, jnp.asarray(md["tok_page_table"]),
        jnp.asarray(md["tok_positions"]), jnp.asarray(md["tok_kv_lens"]),
        window=win,
    )
    T = int(sum(q_lens))
    d = np.abs(np.asarray(out[:T], np.float32)
               - np.asarray(ref_q[:T], np.float32)).max()
    assert d < 3e-2, d
    # and within the int8 rounding envelope of the bf16 pools
    ref = ragged_attention_reference(
        q, kp, vp, jnp.asarray(md["tok_page_table"]),
        jnp.asarray(md["tok_positions"]), jnp.asarray(md["tok_kv_lens"]),
        window=win,
    )
    d_bf16 = np.abs(np.asarray(out[:T], np.float32)
                    - np.asarray(ref[:T], np.float32)).max()
    assert d_bf16 < 8e-2, d_bf16


def test_build_ragged_metadata_overflow():
    """The metadata builder refuses shapes past the bucket's static caps
    (the runner maps these onto BucketOverflowError → engine deferral)."""
    from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

    with pytest.raises(ValueError):
        build_ragged_metadata([16, 17], [0, 0], [16, 17], [[0], [1]], 32)
    with pytest.raises(ValueError):
        build_ragged_metadata([1] * 5, [0] * 5, [1] * 5, [[0]] * 5, 8,
                              max_segs=4)


# -- the ragged kernel's walk (its grid is a work list of live pairs) ---------
# name -> (q_lens, q_starts, kv_lens, T bucket): segments back to back in
# the flat token axis, page size 4, a page table 32 wide
_RAGGED_PS, _RAGGED_MP = 4, 32
_RAGGED_PLANS = {
    # two decode rows, a fresh chunk that starts mid-block, a second chunk
    # with prior pages, 5 rows of tail
    "mixed": ([1, 1, 9, 16], [11, 0, 0, 8], [12, 1, 9, 24], 32),
    # a small live share: one long decode row among short chunks, under a
    # page table much wider than any chunk
    "sparse": ([1, 5, 7, 3], [99, 0, 4, 0], [100, 5, 11, 3], 24),
    # verify_spec's shape: each row's draft is a segment of q_len 2-5 (a
    # tree's branches are more segments), then a plain decode row and a chunk
    "verify": ([3, 5, 2, 4, 1, 6], [20, 7, 33, 33, 9, 0],
               [23, 12, 35, 37, 10, 6], 24),
    # a chunk that fills its blocks exactly, and no tail segment at all
    "full": ([8, 8], [0, 16], [8, 24], 16),
    # no real segment: the bound is 0 and every row comes back 0
    "empty": ([], [], [], 8),
}


def _plan_of(name, shift=0):
    """A plan, every segment `shift` tokens further into its context."""
    q_lens, q_starts, kv_lens, T = _RAGGED_PLANS[name]
    return (q_lens, [n + shift for n in q_starts],
            [n + shift for n in kv_lens], T)


def _ragged_plan(name, seed=23, forked=False, ps=_RAGGED_PS, shift=0):
    from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

    q_lens, q_starts, kv_lens, T = _plan_of(name, shift)
    rng = np.random.default_rng(seed)
    NP = 2 + sum(-(-n // ps) for n in kv_lens)
    free = list(rng.permutation(NP - 2))
    rows = [[int(free.pop()) for _ in range(-(-n // ps))]
            for n in kv_lens]
    if forked:  # a tree's branch: the trunk's pages shared by reference
        rows[3][: len(rows[2]) - 1] = rows[2][:-1]
    md = build_ragged_metadata(q_lens, q_starts, kv_lens, rows, T,
                               max_pages=_RAGGED_MP)
    return md, NP


def _oracle_pairs(name, window, ps=_RAGGED_PS, shift=0):
    """The live (unit, page) pairs by the kernel docstring's rule, from
    the plan itself: a unit is a (q block, segment) overlap of `rows`
    rows from position qpos0; it sees pages first .. last."""
    q_lens, q_starts, kv_lens, _ = _plan_of(name, shift)
    PS, MP, QB = ps, _RAGGED_MP, 8
    pairs, w, lo = [], 0, 0
    for s, ln in enumerate(q_lens):
        hi = lo + ln
        for b in range(lo // QB, (hi - 1) // QB + 1):
            blo, bhi = max(lo, b * QB), min(hi, (b + 1) * QB)
            qpos0, rows = q_starts[s] + blo - lo, bhi - blo
            last = min(min(qpos0 + rows - 1, kv_lens[s] - 1) // PS, MP - 1)
            first = max(qpos0 - window + 1, 0) // PS if window else 0
            pairs += [(w, p) for p in range(min(first, last), last + 1)]
            w += 1
        lo = hi
    return pairs


@pytest.mark.parametrize("window", [None, 0, 6, 16])
@pytest.mark.parametrize("plan", list(_RAGGED_PLANS))
def test_ragged_work_list_matches_oracle(plan, window):
    """The list is the oracle's pairs, units in order and pages
    ascending; pad units and the dummy tail bring none; its capacity is
    a function of (T bucket, MP) alone; the host's count is its bound."""
    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_live_pairs, ragged_work_cap, ragged_work_list,
    )

    md, _ = _ragged_plan(plan)
    T = md["tok_positions"].shape[0]
    win = None if window is None else jnp.int32(window)
    work, n_work, covered = ragged_work_list(
        jnp.asarray(md["meta"]), jnp.asarray(md["seg_kv_lens"]), win,
        _RAGGED_PS, _RAGGED_MP, T)
    want = _oracle_pairs(plan, window or 0)
    got = [(int(e) // _RAGGED_MP, int(e) % _RAGGED_MP)
           for e in np.asarray(work)[: int(n_work)]]
    assert got == want
    assert got == sorted(got)
    assert work.shape == (ragged_work_cap(T) * _RAGGED_MP,)
    # entries past the bound stay inside the tables (an index map may
    # read one step ahead)
    assert np.all(np.asarray(work) // _RAGGED_MP < md["meta"].shape[1])
    assert ragged_live_pairs(md["meta"], md["seg_kv_lens"], window or 0,
                             _RAGGED_PS, _RAGGED_MP) == len(want)
    np.testing.assert_array_equal(np.asarray(covered),
                                  md["tok_positions"] >= 0)
    if plan == "empty":
        assert int(n_work) == 0 and not np.asarray(covered).any()


# name -> (window, softcap, int8 KV)
_RAGGED_VARIANTS = {
    "plain": (None, 0.0, False),
    "window": (6, 0.0, False),
    "softcap": (None, 30.0, False),
    "window-softcap": (6, 30.0, False),
    "int8": (None, 0.0, True),
    "int8-window-softcap": (6, 30.0, True),
}


def _ragged_run(plan, variant, geom=(2, 3, 64), poison=False, forked=False,
                sharded=False, dv=None, sinked=False, ps=_RAGGED_PS, shift=0):
    """(kernel output, reference, real-row mask) of a plan; `poison`: every
    pool page outside the live pairs holds NaN and every page-table entry
    outside them names an unowned NaN page; `dv`: values narrower than
    keys; `sinked`: a sink logit a query head; `ps`, `shift`: pages of `ps`
    tokens, every segment `shift` tokens further into its context."""
    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_attention_reference, ragged_paged_attention,
        ragged_paged_attention_sharded,
    )

    Hk, G, D = geom
    window, softcap, quant = _RAGGED_VARIANTS[variant]
    md, NP = _ragged_plan(plan, forked=forked, ps=ps, shift=shift)
    T = md["tok_positions"].shape[0]
    rng = np.random.default_rng(29)
    q = jnp.asarray(rng.standard_normal((T, Hk, G, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((NP, ps, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NP, ps, Hk, dv or D)),
                     jnp.bfloat16)
    sink = ({"sink": jnp.asarray(rng.standard_normal((Hk, G)), jnp.float32)}
            if sinked else {})
    seg_pt = md["seg_page_table"]
    if poison:
        live = np.zeros(seg_pt.shape, bool)
        for w, p in _oracle_pairs(plan, window or 0, ps, shift):
            live[md["meta"][0, w], p] = True
        dead = jnp.asarray(np.setdiff1d(np.arange(NP), seg_pt[live]))
        kp, vp = kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan)
        seg_pt = np.where(live, seg_pt, NP - 1).astype(np.int32)
    kq, vq = _q_pools(kp, vp) if quant else (kp, vp)
    win = None if window is None else jnp.int32(window)
    seg = (jnp.asarray(seg_pt), jnp.asarray(md["seg_kv_lens"]),
           jnp.asarray(md["meta"]))
    if sharded:
        from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

        out = ragged_paged_attention_sharded(
            q, kq, vq, *seg, make_mesh(MeshConfig(model=2)), window=win,
            softcap=softcap, interpret=True)
    else:
        out = ragged_paged_attention(q, kq, vq, *seg, win, softcap=softcap,
                                     interpret=True, **sink)
    ref = ragged_attention_reference(
        q, kq, vq, jnp.asarray(md["tok_page_table"]),
        jnp.asarray(md["tok_positions"]), jnp.asarray(md["tok_kv_lens"]),
        softcap=softcap, window=win, **sink)
    return (np.asarray(out, np.float32), np.asarray(ref, np.float32),
            md["tok_positions"] >= 0)


def _ragged_close(out, ref, real):
    assert np.all(out[~real] == 0.0)  # no real segment's row: defined, zero
    if real.any():
        d = np.abs(out - ref)[real].max()
        assert d < 3e-2, d


@pytest.mark.parametrize("variant", list(_RAGGED_VARIANTS))
@pytest.mark.parametrize("plan", list(_RAGGED_PLANS))
def test_ragged_walk_matches_reference(plan, variant):
    _ragged_close(*_ragged_run(plan, variant, forked=plan == "verify"))


@pytest.mark.parametrize("geom", [(16, 1, 96), (4, 2, 128), (1, 8, 64),
                                  (1, 1, 128)])
def test_ragged_walk_geometries(geom):
    """phi-3's MHA (G 1, a head dim that is not 128 lanes), a GQA geometry
    and two of one KV head, on the sparse plan under a window."""
    _ragged_close(*_ragged_run("sparse", "window", geom=geom))


# the benchmark's ragged cells: name -> ((Hk, G, D), Dv, a sink, variant).
# Pages of 4 tokens ride 8 a step at every one, so each plan has units whose
# live pages are fewer than the step's tiles; "mixed" has a block that spans
# a segment boundary and a padding tail, "sparse" a window that kills a
# unit's first pages
_CELL_GEOMETRIES = {
    "phi-3": ((32, 1, 96), None, False, "window-softcap"),
    "jamba": ((1, 20, 128), None, False, "softcap"),
    "mimo-global": ((4, 16, 256), 128, False, "softcap"),
    "mimo-window": ((8, 8, 256), 128, True, "window-softcap"),
}


@pytest.mark.parametrize("plan", ["mixed", "sparse", "verify"])
@pytest.mark.parametrize("cell", list(_CELL_GEOMETRIES))
def test_ragged_tile_routine_at_the_cells_geometries(cell, plan):
    """The ragged kernel at the widths the benchmark's cells run it. Three
    take the tile routine, the q block's rows against the step's pages as one
    matrix: columns of other KV heads masked, two head sizes, the sink
    seeding the softmax, softcap before the masks, one KV head with its rows
    as q holds them. phi-3's keeps the float32 product a head."""
    geom, dv, sinked, variant = _CELL_GEOMETRIES[cell]
    _ragged_close(*_ragged_run(plan, variant, geom=geom, dv=dv, sinked=sinked,
                               forked=plan == "verify"))


@pytest.mark.parametrize("cell", ["mimo-global", "mimo-window"])
def test_ragged_tile_routine_reads_no_dead_page_at_two_widths(cell):
    geom, dv, sinked, variant = _CELL_GEOMETRIES[cell]
    clean, _, _ = _ragged_run("sparse", variant, geom=geom, dv=dv, sinked=sinked)
    dirty, _, _ = _ragged_run("sparse", variant, geom=geom, dv=dv,
                              sinked=sinked, poison=True)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("window", [None, 6, 16])
@pytest.mark.parametrize("plan", list(_RAGGED_PLANS))
def test_ragged_walk_steps_and_filled_table(plan, window):
    """`ragged_walk` for a dense pool: the list is the oracle's pairs at the
    step's granularity (units in order, steps ascending, each once), every
    tile a step names is a page some unit of its segment sees (never a dead
    entry, here poisoned to an unowned page), a tile inside the unit's own
    run is the run's own page, and the host's count of live pairs still
    equals the page-granular list's bound."""
    from dynamo_tpu.ops.ragged_paged_attention import (
        _routine_and_tiles, ragged_live_pairs, ragged_walk, ragged_work_cap,
        ragged_work_list,
    )

    md, NP = _ragged_plan(plan)
    T, MP, PS = md["tok_positions"].shape[0], _RAGGED_MP, _RAGGED_PS
    pairs = _oracle_pairs(plan, window or 0)
    live = np.zeros(md["seg_page_table"].shape, bool)
    for w, p in pairs:
        live[md["meta"][0, w], p] = True
    seg_pt = np.where(live, md["seg_page_table"], NP - 1).astype(np.int32)
    pool = jnp.zeros((NP, PS, 2, 64), jnp.bfloat16)
    routine, tiles = _routine_and_tiles((2, 3), pool, pool, False, MP, 8)
    assert (routine, tiles) == ("by_tiles", 8)
    win = None if window is None else jnp.int32(window)
    meta, kvl = jnp.asarray(md["meta"]), jnp.asarray(md["seg_kv_lens"])
    walk = ragged_walk(
        (2, 3), pool, pool, jnp.asarray(seg_pt), kvl, meta, win, T)
    # the walk carries the decision its lists were written for
    assert (walk.routine, walk.tiles) == (routine, tiles)
    work, n_work, covered, pages = (walk.work, walk.n_work, walk.covered,
                                    walk.pages)
    steps = MP // tiles
    assert work.shape == (ragged_work_cap(T) * steps,)
    assert pages.shape == (seg_pt.size,)
    got = [(int(e) // steps, int(e) % steps)
           for e in np.asarray(work)[: int(n_work)]]
    assert got == sorted({(w, p // tiles) for w, p in pairs})
    pages = np.asarray(pages).reshape(seg_pt.shape)
    for w, step in got:
        seg = md["meta"][0, w]
        named = pages[seg, step * tiles:(step + 1) * tiles]
        assert set(named) <= set(seg_pt[seg][live[seg]])
        for p in range(step * tiles, (step + 1) * tiles):
            if (w, p) in pairs:
                assert pages[seg, p] == seg_pt[seg, p]
    np.testing.assert_array_equal(np.asarray(covered),
                                  md["tok_positions"] >= 0)
    # an int8 pool keeps the float32 body and its page-granular list
    q8 = {"q": jnp.zeros((NP, PS, 2, 64), jnp.int8),
          "s": jnp.zeros((NP, PS, 2), jnp.float32)}
    assert _routine_and_tiles((2, 3), q8["q"], q8["q"], True, MP, 8) == (
        "by_heads", 1)
    by_heads = ragged_walk((2, 3), q8, q8, jnp.asarray(seg_pt), kvl, meta,
                           win, T)
    by_pages = ragged_work_list(meta, kvl, win, PS, MP, T)
    assert by_heads.pages is None
    assert (by_heads.routine, by_heads.tiles) == ("by_heads", 1)
    for a, b in zip((by_heads.work, by_heads.n_work, by_heads.covered),
                    by_pages):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ragged_live_pairs(md["meta"], md["seg_kv_lens"], window or 0,
                             PS, MP) == int(by_pages[1]) == len(pairs)


def test_ragged_page_routine_is_a_rule_of_its_own_shapes():
    """Quantized, or G = 1 at 32 KV heads or more (Hk times the useful
    work with no group to fill the rows): the product a head. It takes
    nothing of what the decode kernel's `by_rows` rule hangs on (the pool's
    dtype, a sink, one width or two)."""
    from dynamo_tpu.ops.ragged_paged_attention import ragged_page_routine

    assert [ragged_page_routine(Hk, 1, False)
            for Hk in (1, 4, 8, 16, 24, 32, 40)] == [
        "by_tiles"] * 5 + ["by_heads"] * 2
    assert {ragged_page_routine(Hk, G, False) for Hk in (1, 2, 8, 32)
            for G in (2, 3, 8, 20)} == {"by_tiles"}
    assert ragged_page_routine(1, 20, True) == "by_heads"
    assert ragged_page_routine(8, 4, True) == "by_heads"


def test_walks_take_the_heads_of_one_call_not_the_pools():
    """A walk is built outside `shard_map`, on pools that still have every
    head: its pages a step come from `heads`, and ride the walk."""
    from dynamo_tpu.ops.paged_attention import decode_walk, page_bytes
    from dynamo_tpu.ops.ragged_paged_attention import (
        build_ragged_metadata, ragged_walk,
    )

    whole = jnp.zeros((2, 4, 64, 8, 128), jnp.bfloat16)
    shard = jnp.zeros((2, 4, 64, 4, 128), jnp.bfloat16)
    assert page_bytes(4, whole, whole) == page_bytes(4, shard, shard) == 1 << 17
    assert page_bytes(8, whole, whole) == 1 << 18
    pt, kv = jnp.zeros((2, 8), jnp.int32), jnp.asarray([70, 300], jnp.int32)
    for pool in (whole, shard):
        walk = decode_walk((4, 3), pool, pool, pt, kv, None, False)
        assert (walk.routine, walk.tiles) == ("by_tiles", 8)
    assert decode_walk((8, 3), whole, whole, pt, kv, None, False).tiles == 4
    md = build_ragged_metadata([1, 7], [69, 0], [70, 7], [[1, 2], [3]], 8,
                               max_pages=8)
    seg = tuple(jnp.asarray(md[k]) for k in
                ("seg_page_table", "seg_kv_lens", "meta"))
    for pool in (whole, shard):
        walk = ragged_walk((4, 3), pool, pool, *seg, None, 8)
        assert (walk.routine, walk.tiles) == ("by_tiles", 8)
    assert ragged_walk((8, 3), whole, whole, *seg, None, 8).tiles == 2


def test_ragged_step_tiles_sees_the_rows():
    """Pages a step: `step_tiles`' count from the page's bytes, halved while
    the q block's score block passes SCORE_BYTES; the cells' geometries."""
    from dynamo_tpu.ops.ragged_paged_attention import _routine_and_tiles

    def step(Hk, G, D, Dv, MP, q_block=8, PS=64):
        kq = jax.ShapeDtypeStruct((2, 8, PS, Hk, D), jnp.bfloat16)
        vq = jax.ShapeDtypeStruct((2, 8, PS, Hk, Dv), jnp.bfloat16)
        return _routine_and_tiles((Hk, G), kq, vq, False, MP, q_block)

    assert step(4, 16, 256, 128, 96) == ("by_tiles", 2)  # mimo-global
    assert step(8, 8, 256, 128, 96) == ("by_tiles", 1)  # mimo-window
    assert step(1, 20, 128, 128, 64) == ("by_tiles", 8)  # ai21-jamba2-3b
    # phi-3: G 1 at 32 KV heads or more keeps the product a head
    assert step(32, 1, 96, 96, 64) == ("by_heads", 1)
    assert step(8, 1, 128, 128, 64) == ("by_tiles", 4)  # G 1 at fewer
    assert step(8, 4, 128, 128, 64) == ("by_tiles", 2)
    assert step(8, 4, 128, 128, 64, PS=16) == ("by_tiles", 8)
    assert step(4, 16, 256, 128, 96, q_block=4) == ("by_tiles", 4)
    assert step(8, 3, 128, 128, 7) == ("by_tiles", 1)  # a table no count divides


_MQA = (1, 20, 128)  # ai21-jamba2-3b: the page a [PS, D] tile of a 4-d pool


@pytest.mark.parametrize("variant", list(_RAGGED_VARIANTS))
@pytest.mark.parametrize("plan", list(_RAGGED_PLANS))
def test_ragged_walk_one_kv_head_matches_reference(plan, variant):
    _ragged_close(*_ragged_run(plan, variant, geom=_MQA,
                               forked=plan == "verify"))


@pytest.mark.parametrize("geom", [(2, 3, 64), _MQA], ids=["gqa", "mqa"])
@pytest.mark.parametrize("variant", ["plain", "window"])
@pytest.mark.parametrize("plan", ["mixed", "sparse", "verify"])
def test_ragged_walk_reads_only_live_pairs(plan, variant, geom):
    """With every dead page and every dead page-table entry poisoned the
    result is the clean one, bit for bit: no dead pair was visited."""
    clean, _, real = _ragged_run(plan, variant, geom=geom)
    dirty, _, _ = _ragged_run(plan, variant, geom=geom, poison=True)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
@pytest.mark.parametrize("variant", ["window", "int8"])
def test_ragged_walk_sharded(variant):
    _ragged_close(*_ragged_run("sparse", variant, geom=(2, 4, 64),
                               sharded=True))


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs multi-device mesh")
@pytest.mark.parametrize("plan,variant", [("mixed", "plain"),
                                          ("sparse", "window")])
def test_ragged_walk_sharded_takes_a_shards_pages_a_step(plan, variant):
    """Llama-3.2-3B's heads (Hk 8, G 3, D 128) at pages of 64 over two
    shards. The walk is built outside `shard_map`, where the pool still has
    all 8 KV heads (a 256 KB page: 2 pages a step under the score block's
    bound); a shard's kernel sees 4 (128 KB: 8). The lists are written for
    the shard's heads and the kernel reads the count off the walk, so every
    segment of ~10 pages is read at the granularity it was listed at;
    poisoned: no dead page, no dead entry."""
    from dynamo_tpu.ops.ragged_paged_attention import _routine_and_tiles

    pool = jax.ShapeDtypeStruct((4, 64, 8, 128), jnp.bfloat16)
    local = jax.ShapeDtypeStruct((4, 64, 4, 128), jnp.bfloat16)
    assert _routine_and_tiles((8, 3), pool, pool, False, _RAGGED_MP,
                              8) == ("by_tiles", 2)
    assert _routine_and_tiles((4, 3), pool, pool, False, _RAGGED_MP,
                              8) == _routine_and_tiles(
        (4, 3), local, local, False, _RAGGED_MP, 8) == ("by_tiles", 8)
    kw = dict(geom=(8, 3, 128), sharded=True, ps=64, shift=64 * 9 + 5)
    clean, ref, real = _ragged_run(plan, variant, **kw)
    _ragged_close(clean, ref, real)
    dirty, _, _ = _ragged_run(plan, variant, poison=True, **kw)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


def test_ragged_grid_is_one_traced_bound():
    """One dynamic grid dimension, and the call's static shapes (the
    list's capacity among them) hang on (T bucket, MP) only, not on the
    plan: one program a T bucket."""
    from dynamo_tpu.ops.ragged_paged_attention import (
        build_ragged_metadata, ragged_paged_attention, ragged_work_cap,
    )

    T = 24

    def call_of(plan):
        q_lens, q_starts, kv_lens, _ = _RAGGED_PLANS[plan]
        rows = [[1] * -(-n // _RAGGED_PS) for n in kv_lens]
        md = build_ragged_metadata(q_lens, q_starts, kv_lens, rows, T,
                                   max_pages=_RAGGED_MP)
        q = jnp.zeros((T, 2, 2, 64), jnp.bfloat16)
        pool = jnp.zeros((4, _RAGGED_PS, 2, 64), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(functools.partial(
            ragged_paged_attention, interpret=True))(
            q, pool, pool, jnp.asarray(md["seg_page_table"]),
            jnp.asarray(md["seg_kv_lens"]), jnp.asarray(md["meta"]))
        (eqn,) = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                  if e.primitive.name == "pallas_call"]
        gm = eqn.params["grid_mapping"]
        return (gm.grid, gm.num_dynamic_grid_bounds,
                [v.aval.shape for v in eqn.invars])

    assert call_of("sparse") == call_of("verify") == call_of("empty")
    grid, n_dynamic, shapes = call_of("sparse")
    assert n_dynamic == 1 and len(grid) == 1
    # the list, at the tile routine's 8 of these 4 KB pages a step
    assert (ragged_work_cap(T) * _RAGGED_MP // 8,) in shapes


# -- layer-stacked pools: the kernels read [L, NP, PS, Hk, D] at a layer ----
_STACK_L = 4


def _stacked_case(kernel, *, quant=False, window=None, softcap=0.0,
                  sharded=False, seed=31, heads=(2, 3)):
    """One kernel over a stacked pool with different data in every layer.
    Returns (run, ref): run(k_pool, v_pool, layer) calls the kernel
    (`layer` None for a per-layer pool), ref(layer) is the jnp path on
    that layer's slab; both give the rows that hold real tokens."""
    from dynamo_tpu.ops.ragged_paged_attention import (
        ragged_attention_reference, ragged_paged_attention,
        ragged_paged_attention_sharded,
    )

    rng = np.random.default_rng(seed)
    L, (Hk, G), D, NP, PS, MP = _STACK_L, heads, 64, 48, 8, 8
    kp = jnp.asarray(rng.standard_normal((L, NP, PS, Hk, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((L, NP, PS, Hk, D)), jnp.bfloat16)
    if quant:
        kp, vp = _q_pools(kp, vp)
    win = None if window is None else jnp.int32(window)
    kw = dict(softcap=softcap, interpret=True)
    if sharded:
        from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(model=2))
    slab = lambda pool, l: jax.tree.map(lambda a: a[l], pool)  # noqa: E731

    if kernel == "decode":
        from dynamo_tpu.ops.paged_attention import (
            decode_paged_attention_sharded,
        )

        B = 4
        q = jnp.asarray(rng.standard_normal((B, Hk, G, D)), jnp.bfloat16)
        pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP)
                         .astype(np.int32))
        kv = jnp.asarray([5, 17, 64, 1], jnp.int32)

        def run(k, v, layer):
            if sharded:
                return decode_paged_attention_sharded(
                    q, k, v, pt, kv, mesh, window=win, layer=layer, **kw)
            return decode_paged_attention(q, k, v, pt, kv, win, layer, **kw)

        def ref(l):
            return paged_attention_jnp(
                q[:, None], slab(kp, l), slab(vp, l), pt, (kv - 1)[:, None],
                kv, softcap=softcap, window=win)[:, 0]

    elif kernel == "latent":  # one pool, the values its first dc columns
        from dynamo_tpu.ops.mla_attention import (
            decode_mla_attention, decode_mla_attention_sharded,
        )

        B, dc = 4, 48
        q = jnp.asarray(rng.standard_normal((B, G, D)), jnp.bfloat16)
        pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP)
                         .astype(np.int32))
        kv = jnp.asarray([5, 17, 64, 1], jnp.int32)
        kw = dict(dc=dc, scale=0.11, interpret=True)

        def run(k, v, layer):
            if sharded:
                return decode_mla_attention_sharded(q, k, pt, kv, mesh,
                                                    layer=layer, **kw)
            return decode_mla_attention(q, k, pt, kv, layer, **kw)

        def ref(l):
            lat = slab(kp, l)
            if quant:
                values = {"q": lat["q"][..., :dc], "s": lat["s"]}
            else:
                values = lat[..., :dc]
            return paged_attention_jnp(
                q[:, None, None], lat, values, pt, (kv - 1)[:, None], kv,
                scale=0.11)[:, 0, 0]

    elif kernel == "prefill":
        from dynamo_tpu.ops.flash_prefill import (
            prefill_paged_attention_sharded,
        )

        B, S = 2, 16
        q = jnp.asarray(rng.standard_normal((B, S, Hk, G, D)), jnp.bfloat16)
        pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP)
                         .astype(np.int32))
        qs, ql = jnp.asarray([24, 0], jnp.int32), jnp.asarray([16, 16], jnp.int32)
        kv = qs + ql
        pos = qs[:, None] + jnp.arange(S)[None]

        def run(k, v, layer):
            if sharded:
                return prefill_paged_attention_sharded(
                    q, k, v, pt, qs, ql, kv, mesh, window=win, layer=layer,
                    q_block=8, **kw)
            return prefill_paged_attention(
                q, k, v, pt, qs, ql, kv, win, layer, q_block=8, **kw)

        def ref(l):
            return paged_attention_jnp(
                q, slab(kp, l), slab(vp, l), pt, pos, kv, softcap=softcap,
                window=win)

    else:
        q, _kp, _vp, md, (q_lens, *_rest) = _ragged_case(
            seed, Hk=Hk, G=G, NP=NP, PS=PS, MP=MP)
        T = int(sum(q_lens))
        seg = [jnp.asarray(md[k]) for k in
               ("seg_page_table", "seg_kv_lens", "meta")]
        tok = [jnp.asarray(md[k]) for k in
               ("tok_page_table", "tok_positions", "tok_kv_lens")]

        def run(k, v, layer):
            if sharded:
                return ragged_paged_attention_sharded(
                    q, k, v, *seg, mesh, window=win, layer=layer, **kw)[:T]
            return ragged_paged_attention(q, k, v, *seg, win, layer, **kw)[:T]

        def ref(l):
            return ragged_attention_reference(
                q, slab(kp, l), slab(vp, l), *tok, softcap=softcap,
                window=win)[:T]

    return kp, vp, run, ref


_STACKED_CASES = {
    "decode": dict(kernel="decode"),
    "prefill": dict(kernel="prefill"),
    "ragged": dict(kernel="ragged"),
    "decode-int8": dict(kernel="decode", quant=True),
    "prefill-int8-window": dict(kernel="prefill", quant=True, window=6,
                                softcap=15.0),
    "ragged-int8": dict(kernel="ragged", quant=True),
    "decode-window": dict(kernel="decode", window=11, softcap=20.0),
    "ragged-window": dict(kernel="ragged", window=16, softcap=30.0),
    "decode-sharded": dict(kernel="decode", sharded=True),
    "prefill-sharded-window": dict(kernel="prefill", sharded=True, window=5),
    "ragged-sharded-int8": dict(kernel="ragged", sharded=True, quant=True),
    # one KV head: the 4-d view of the stack, at the same traced layer
    "decode-mqa-window": dict(kernel="decode", heads=(1, 20), window=11,
                              softcap=20.0),
    "ragged-mqa": dict(kernel="ragged", heads=(1, 20)),
    "decode-mqa-int8": dict(kernel="decode", heads=(1, 20), quant=True),
    "ragged-sharded-window": dict(kernel="ragged", sharded=True, window=16),
    # latent attention's decode kernel on the same walk: its one pool
    # [L, NP, PS, 1, Dl], 8 pages a step, 20 query heads
    "latent": dict(kernel="latent", heads=(1, 20)),
    "latent-int8": dict(kernel="latent", heads=(1, 20), quant=True),
    "latent-sharded": dict(kernel="latent", heads=(1, 20), sharded=True),
}


@pytest.mark.parametrize("layer", [0, 2, _STACK_L - 1])
@pytest.mark.parametrize("case", list(_STACKED_CASES))
def test_kernels_read_stacked_pool_at_layer(case, layer):
    """Each kernel takes the stacked pool and a traced layer (a
    scalar-prefetch operand beside page_table/kv_lens/window) and must
    read THAT layer's pages: every layer holds different data, so a
    kernel that read another layer, or took the window for the layer,
    misses the jnp reference on pool[layer]."""
    cfg = _STACKED_CASES[case]
    if cfg.get("sharded") and len(jax.devices()) < 2:
        pytest.skip("needs multi-device mesh")
    kp, vp, run, ref = _stacked_case(**cfg)
    out = run(kp, vp, jnp.int32(layer))
    want = ref(layer)
    d = np.abs(np.asarray(out, np.float32) - np.asarray(want, np.float32)).max()
    assert d < 3e-2, d
    other = ref((layer + 1) % _STACK_L)
    d_other = np.abs(np.asarray(out, np.float32)
                     - np.asarray(other, np.float32)).max()
    assert d_other > 0.2, d_other  # the layers really differ


@pytest.mark.parametrize(
    "case", ["decode", "prefill", "ragged", "decode-int8", "ragged-window",
             "decode-sharded", "decode-mqa-window", "ragged-mqa", "latent",
             "latent-int8", "latent-sharded"],
)
def test_per_layer_pool_is_the_one_layer_stack(case):
    """A pool of rank 4 is viewed as `pool[None]` at layer 0: the same
    program, so the two calls agree bit for bit."""
    cfg = _STACKED_CASES[case]
    if cfg.get("sharded") and len(jax.devices()) < 2:
        pytest.skip("needs multi-device mesh")
    kp, vp, run, _ref = _stacked_case(**cfg)
    k1, v1 = jax.tree.map(lambda a: a[1], (kp, vp))
    per_layer = run(k1, v1, None)
    stacked = run(*jax.tree.map(lambda a: a[None], (k1, v1)), jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(per_layer, np.float32),
                                  np.asarray(stacked, np.float32))
    # and the same bits as reading layer 1 of the full stack
    np.testing.assert_array_equal(
        np.asarray(per_layer, np.float32),
        np.asarray(run(kp, vp, jnp.int32(1)), np.float32))


def test_stacked_pool_operand_contract():
    """A stacked pool without a layer, or a per-layer pool with one, is a
    caller's slip, not a default."""
    kp, vp, run, _ref = _stacked_case("decode")
    with pytest.raises(ValueError):
        run(kp, vp, None)
    with pytest.raises(ValueError):
        run(kp[0], vp[0], jnp.int32(0))


# -- the model's layer scan hands the kernels the stacked pool --------------
def _interpret_attention_kernels(monkeypatch):
    import dynamo_tpu.ops.flash_prefill as fp_ops
    import dynamo_tpu.ops.paged_attention as pa_ops
    import dynamo_tpu.ops.ragged_paged_attention as rg_ops

    for mod, name in ((pa_ops, "decode_paged_attention"),
                      (fp_ops, "prefill_paged_attention"),
                      (rg_ops, "ragged_paged_attention")):
        monkeypatch.setattr(
            mod, name, functools.partial(getattr(mod, name), interpret=True))


def _three_layer_forward_case(mode):
    """forward() inputs for a 3-layer model in one of the three Pallas
    modes, over a pool that already holds a 6-token context for row 0.
    Returns (slab shape, call) with call(attn_impl) -> (logits, k_pool,
    v_pool)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata

    c = get_config("tiny").with_(n_layers=3)
    # f32 weights and pool: the two paths then differ by reduction order
    # alone, and a wrong layer or page cannot hide in bf16 rounding
    p = llama.init_params(c, jax.random.PRNGKey(4), dtype=jnp.float32)
    NP, PS, MP = 16, 4, 4
    k0, v0 = llama.make_kv_pool(c, NP, PS, dtype=jnp.float32)
    rows = [[3, 9, 1, 12], [7, 2, 14, 5]]
    pt = jnp.asarray(rows, jnp.int32)
    ctx = [5, 9, 2, 7, 1, 3]
    _, k0, v0 = llama.forward(
        c, p, jnp.asarray([ctx]), jnp.arange(6)[None], k0, v0, pt[:1],
        jnp.asarray([6]),
    )
    if mode == "decode":
        args = (jnp.asarray([[8]]), jnp.asarray([[6]]), k0, v0, pt[:1],
                jnp.asarray([7]))
        kw = {}
    elif mode == "prefill":  # a chunk on prior context + a fresh, padded one
        toks = jnp.asarray([[8, 4, 6, 2, 9, 1, 7, 3], [6, 5, 4, 3, 2, 0, 0, 0]])
        pos = jnp.asarray([list(range(6, 14)), [0, 1, 2, 3, 4, -1, -1, -1]])
        args = (toks, pos, k0, v0, pt, jnp.asarray([14, 5]))
        kw = {}
    else:  # ragged: row 0 decodes one token, row 1 brings a 9-token chunk
        md = build_ragged_metadata([1, 9], [6, 0], [7, 9], rows, 16,
                                   max_pages=MP)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :10] = [8, 6, 5, 4, 3, 2, 9, 1, 7, 3]
        args = (jnp.asarray(toks), jnp.asarray(md["tok_positions"])[None],
                k0, v0, jnp.asarray(md["tok_page_table"]),
                jnp.asarray(md["tok_kv_lens"]))
        kw = dict(
            last_index=jnp.asarray(md["last_index"]),
            ragged=tuple(jnp.asarray(md[k]) for k in
                         ("seg_page_table", "seg_kv_lens", "meta")),
        )

    def call(attn_impl):
        return llama.forward(c, p, *args, attn_impl=attn_impl, **kw)

    return (NP, PS, c.n_kv_heads, c.head_dim), call


def _slab_equations(jaxpr, slab, found=None):
    """Every equation, at any depth, whose output is one layer's slab of a
    pool ([NP, PS, Hk, D], with or without a leading 1)."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            if shape in (slab, (1,) + slab):
                found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _slab_equations(sub, slab, found)
    return found


@pytest.mark.parametrize("mode", ["decode", "prefill", "ragged"])
def test_pallas_layer_scan_slices_no_pool_slab(mode, monkeypatch):
    """The mechanism, where a CPU can see it: with attn_impl="pallas" the
    traced layer scan produces no value of a pool slab's shape (the
    kernels index the stacked pool themselves); the jnp path, which
    gathers pages from `pool[l]`, does — so the check can see one."""
    _interpret_attention_kernels(monkeypatch)
    slab, call = _three_layer_forward_case(mode)
    on_jnp = _slab_equations(jax.make_jaxpr(lambda: call("jnp"))().jaxpr, slab)
    assert any(n in ("dynamic_slice", "gather") for n in on_jnp), on_jnp
    on_pallas = _slab_equations(
        jax.make_jaxpr(lambda: call("pallas"))().jaxpr, slab)
    assert on_pallas == []


@pytest.mark.parametrize("mode", ["decode", "prefill", "ragged"])
def test_three_layer_forward_pallas_matches_jnp(mode, monkeypatch):
    """A whole forward over 3 layers through each Pallas kernel (interpret)
    == the jnp path: logits, and the pools both wrote."""
    _interpret_attention_kernels(monkeypatch)
    _slab, call = _three_layer_forward_case(mode)
    ref, k_ref, v_ref = call("jnp")
    got, k_got, v_got = call("pallas")
    if mode == "prefill":  # padded rows of the fresh chunk carry garbage
        ref, got = (jnp.concatenate([x[0], x[1, :5]]) for x in (ref, got))
    for a, b in ((got, ref), (k_got, k_ref), (v_got, v_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
