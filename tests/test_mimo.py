"""MiMo-V2-Flash's decoder (models/mimo.py): the plain reference's sink
softmax and router against transformers' gpt_oss and deepseek_v3 forms, the
program against the reference, and a sequence's two page tables through every
path it takes: chunked prefill, the decode loop, the ragged mixed step,
window pages freed as they leave the window, preemption, cancellation and
reuse. Seeded random weights, small sizes, float32 on the CPU (so the
tolerances are float32 rounding: 2e-4 on a logprob where two float32 programs
order their sums differently).
"""

import asyncio
import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import worker
from dynamo_tpu.engine.kv_pool import NoSpace, PagePool
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.runner_api import Runner, refusal
from dynamo_tpu.engine.scheduler import (
    PrefillPlan, Scheduler, SeqState, Sequence,
)
from dynamo_tpu.engine.side_cache import WindowPages
from dynamo_tpu.engine.weights import config_from_hf
from dynamo_tpu.models import llama, mimo
from dynamo_tpu.models.config import ModelConfig, get_config, mean_over_layers
from dynamo_tpu.models.toolkit import make_kv_pool, paged_attention_jnp
from dynamo_tpu.ops.flash_prefill import prefill_paged_attention
from dynamo_tpu.ops.paged_attention import decode_paged_attention
from dynamo_tpu.ops.ragged_paged_attention import (
    build_ragged_metadata, ragged_paged_attention,
)
from dynamo_tpu.runtime.context import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # float32 programs that order their sums differently


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/mimo_v2_decoder.py", "_mimo_reference")
C = get_config("tiny-mimo")
PS, MP, W = 8, 12, C.sliding_window


def _model(c=C) -> dict:
    m = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    m["layer_pattern"] = list(c.layer_pattern)
    return m


MODEL = _model()


def _params(seed=0, dtype=jnp.float32, c=C):
    """The preset's tree with the fills made random too (the sinks, the
    router's bias, the norm weights), as a checkpoint has them."""
    params = llama.init_params(c, jax.random.PRNGKey(seed), dtype)
    rng = np.random.default_rng(seed + 1)
    params["layers"]["router_bias"] = jnp.asarray(
        rng.normal(0, 0.1, params["layers"]["router_bias"].shape), jnp.float32)
    params["attn_window"]["sink_bias"] = jnp.asarray(
        rng.normal(0.5, 1.0, params["attn_window"]["sink_bias"].shape), jnp.float32)
    for group in ("layers", "layers_dense"):
        for k in ("attn_norm", "mlp_norm"):
            params[group][k] = jnp.asarray(
                rng.uniform(0.5, 1.5, params[group][k].shape), jnp.float32)
    return params


@pytest.fixture(scope="module")
def params():
    return _params()


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, C.vocab_size, size=n).astype(np.int32)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1))


# -- the reference against transformers ---------------------------------------


def test_sink_softmax_agrees_with_gpt_oss():
    """The reference's sink column is gpt_oss's: the sink concatenated to the
    logits, one softmax, the column dropped (float32)."""
    torch = pytest.importorskip("torch")
    from transformers.models.gpt_oss.modeling_gpt_oss import eager_attention_forward

    rng = np.random.default_rng(0)
    H, Hk, S, d = 4, 2, 9, 8
    q, k, v = (rng.normal(size=(1, h, S, d)).astype(np.float32) for h in (H, Hk, Hk))
    sinks = rng.normal(size=H).astype(np.float32)
    mask = np.where(np.tril(np.ones((S, S), bool)), 0.0, -np.inf).astype(np.float32)

    class M:
        num_key_value_groups, training = H // Hk, False

    M.sinks = torch.tensor(sinks)
    want, probs = eager_attention_forward(
        M, *(torch.tensor(a) for a in (q, k, v)), torch.tensor(mask)[None, None], d ** -0.5)
    kk = np.repeat(k[0], H // Hk, axis=0)
    scores = jnp.einsum("hsd,htd->hst", q[0], kk) * d ** -0.5
    scores = jnp.where(jnp.asarray(mask == 0)[None], scores, -jnp.inf)
    a = ref.sink_softmax(scores, jnp.asarray(sinks))
    np.testing.assert_allclose(np.asarray(a), probs[0].numpy(), atol=2e-6)
    out = jnp.einsum("hst,htd->shd", a, np.repeat(v[0], H // Hk, axis=0))
    np.testing.assert_allclose(np.asarray(out), want[0].numpy(), atol=2e-6)


def test_router_agrees_with_deepseek_v3():
    """selection + mixing_weights are DeepseekV3TopkRouter's picks and
    weights at n_group 1: sigmoid, the bias in the selection alone, the
    weights renormalised over the picks."""
    torch = pytest.importorskip("torch")
    from transformers.models.deepseek_v3.configuration_deepseek_v3 import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import DeepseekV3TopkRouter

    rng = np.random.default_rng(1)
    E, n, k = 16, 8, 2
    router = DeepseekV3TopkRouter(DeepseekV3Config(
        hidden_size=E, n_routed_experts=n, num_experts_per_tok=k, n_group=1,
        topk_group=1, norm_topk_prob=True, routed_scaling_factor=1.0))
    w = rng.normal(size=(n, E)).astype(np.float32)
    bias = rng.normal(0, 0.3, size=n).astype(np.float32)
    router.weight.data = torch.tensor(w)
    router.e_score_correction_bias.data = torch.tensor(bias)
    x = rng.normal(size=(11, E)).astype(np.float32)
    idx, wts = router(torch.tensor(x))
    model = {"moe_scoring": "sigmoid", "moe_norm_topk": True, "moe_routed_scale": 1.0,
             "n_experts_active": k}
    sel, mix, _ = ref._m4._base.route(jnp.asarray(x) @ jnp.asarray(w.T), jnp.asarray(bias), model)
    order = np.argsort(idx.numpy(), axis=-1)
    mine = np.argsort(np.asarray(sel), axis=-1)
    np.testing.assert_array_equal(np.take_along_axis(idx.numpy(), order, -1),
                                  np.take_along_axis(np.asarray(sel), mine, -1))
    np.testing.assert_allclose(np.take_along_axis(wts.detach().numpy(), order, -1),
                               np.take_along_axis(np.asarray(mix), mine, -1), atol=1e-6)


# -- configuration and tree ----------------------------------------------------


def test_the_config_says_the_layers():
    assert C.has_window_pool and C.kv_layers == 2
    assert C.global_layers == (0, 5) and C.window_layers == (1, 2, 3, 4, 6)
    assert (C.head_dim, C.value_dim) == (24, 16)
    c = ModelConfig(**{**_model(), "layer_pattern": [0, 1, 1, 1, 1, 0, 1]})  # a list, from JSON
    assert c.layer_pattern == C.layer_pattern and hash(c) == hash(C)
    assert mean_over_layers(C, 70, 7) == round((2 * 70 + 5 * 7) / 7)
    for bad in ({"layer_pattern": (0, 1)}, {"n_kv_heads_window": 3},
                {"sliding_window": 0}, {"rope_partial_dims": 7}):
        with pytest.raises(ValueError, match="layer_pattern"):
            C.with_(**bad)
    with pytest.raises(ValueError, match="state the pattern"):
        ModelConfig(sink_window=True)
    big = get_config("mimo-v2-flash")
    assert len(big.global_layers) == 9 and len(big.window_layers) == 39
    assert big.global_layers[:3] == (0, 5, 11)


def test_init_params_fills_the_sinks_and_draws_every_matrix():
    """benchmark/serve.py draws what depends on the key and keeps what does
    not: `sink_bias` (the ramp), `router_bias` and the norms are fills; every
    drawn leaf is `[..., in, out]`."""
    jaxpr = jax.make_jaxpr(lambda k: llama.init_params(C, k, jnp.float32))(
        jax.random.PRNGKey(0)).jaxpr
    keyed = {id(v) for v in jaxpr.invars}
    for eqn in jaxpr.eqns:
        if any(id(v) in keyed for v in eqn.invars):
            keyed.update(id(v) for v in eqn.outvars)
    tree = llama.init_params(C, jax.random.PRNGKey(0), jnp.float32)
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    fills = {p for p, v in zip(paths, jaxpr.outvars) if id(v) not in keyed}
    assert fills == {"['norm_f']", "['attn_window']['sink_bias']", "['layers']['attn_norm']",
                     "['layers']['mlp_norm']", "['layers']['router_bias']",
                     "['layers_dense']['attn_norm']", "['layers_dense']['mlp_norm']"}
    sink = np.asarray(tree["attn_window"]["sink_bias"])
    assert sink.shape == (5, 4) and sink[0, 0] == -1.0 and sink[0, -1] == 4.0
    assert tree["attn_global"]["wk"].shape == (2, 64, 24) and "sink_bias" not in tree["attn_global"]
    assert tree["attn_window"]["wv"].shape == (5, 64, 32)
    assert tree["layers"]["we_gate"].shape == (6, 8, 64, 32)
    with pytest.raises(NotImplementedError, match="models/mimo.forward"):
        llama.forward(C, tree, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32),
                      None, None, None, None)


def test_loader_takes_the_published_keys(tmp_path):
    row = json.load(open(os.path.join(ROOT, "benchmark/configs/mimo-v2-flash.json")))
    hf = {k: v for k, v in row.items() if k not in (
        "source", "reduced", "published", "deployment", "assumed", "model",
        "server_flags", "reference", "correct_tolerance", "correct_routing_margin", "rehearse")}
    hf.update(row["published"])
    (tmp_path / "config.json").write_text(json.dumps(hf))
    c = config_from_hf(str(tmp_path), name="mimo-v2-flash")
    assert c == get_config("mimo-v2-flash").with_(max_seq_len=c.max_seq_len)
    assert c.max_seq_len == 262144


# -- the program against the reference ------------------------------------------


def _pools(np_global=24, np_window=24, poison=0.0):
    kp, vp = make_kv_pool(C, np_global, PS, jnp.float32)
    wp = mimo.make_window_pool(C, np_window, PS, jnp.float32)
    if poison:
        wp = jax.tree.map(lambda a: a + poison, wp)
    return kp, vp, wp


class _Tables:
    """A sequence's two page tables as the scheduler keeps them: the global
    one whole, the window one with the pages that left the window freed
    (scratch page 0) and, if asked, poisoned in the pool."""

    def __init__(self, pages, wpages):
        self.pages, self.free_w = list(pages), list(wpages)
        self.wpages, self.lo, self.freed = [0] * len(pages), 0, []

    def cover(self, first_query, last_query):
        lo = mimo.window_first_live_page(first_query, W, PS)
        for j in range(self.lo, lo):
            if self.wpages[j]:
                self.freed.append(self.wpages[j])
                self.wpages[j] = 0
        self.lo = max(self.lo, lo)
        for j in range(self.lo, last_query // PS + 1):
            if not self.wpages[j]:
                self.wpages[j] = self.free_w.pop(0)

    def rows(self):
        pad = lambda r: jnp.asarray([r + [0] * (MP - len(r))], jnp.int32)
        return pad(self.pages), pad(self.wpages)


def _poison(wp, pages, value=1e4):
    idx = jnp.asarray(pages, jnp.int32)
    return jax.tree.map(lambda a: a.at[:, idx].set(value), wp) if pages else wp


def _chunk(params, pools, t: _Tables, toks, start, n, attn_impl="jnp", poison=True):
    kp, vp, wp = pools
    S = max(32, -(-n // 8) * 8)
    t.cover(start, start + n - 1)
    if poison:
        wp = _poison(wp, t.freed)
    pt, wpt = t.rows()
    pad = np.zeros(S, np.int32)
    pad[:n] = toks[start:start + n]
    pos = np.full(S, -1, np.int32)
    pos[:n] = np.arange(start, start + n)
    lg, kp, vp, wp = mimo.forward(
        C, params, jnp.asarray(pad[None]), jnp.asarray(pos[None]), kp, vp, pt,
        jnp.asarray([start + n]), last_index=jnp.int32(n - 1), state=wp, slots=wpt,
        attn_impl=attn_impl)
    return lg[0, 0], (kp, vp, wp)


def _interpreted_kernels(monkeypatch):
    """attn_impl="pallas" on the CPU: the three attention kernels and the
    hit experts' work-list kernel in interpret mode."""
    from dynamo_tpu.ops import flash_prefill as fp_ops
    from dynamo_tpu.ops import moe_experts
    from dynamo_tpu.ops import paged_attention as pa_ops
    from dynamo_tpu.ops import ragged_paged_attention as rg_ops

    for mod, name in ((pa_ops, "decode_paged_attention"), (rg_ops, "ragged_paged_attention"),
                      (fp_ops, "prefill_paged_attention"), (moe_experts, "routed_experts")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name), interpret=True))


def test_full_forward_agrees_with_the_reference(params):
    toks = _tokens(44, 1)
    kp, vp, wp = _pools()
    pt = jnp.arange(1, MP + 1, dtype=jnp.int32)[None]
    lg, _, _, sel, listed, _ = mimo.forward(
        C, params, jnp.asarray(toks[None]), jnp.arange(44)[None], kp, vp, pt,
        jnp.asarray([44]), state=wp, slots=pt, return_routed=True, return_listed=True)
    want = ref.logprobs_at(MODEL, params, toks, list(range(44)))
    assert np.abs(_logp(lg[0]) - want).max() < TOL
    assert sel.shape == (6, 1, 44, 2) and listed.shape == (6,)
    own = ref.own_picks(MODEL, params, toks)  # [S, L_moe, k]
    np.testing.assert_array_equal(np.sort(own, -1),
                                  np.sort(np.asarray(sel[:, 0]).transpose(1, 0, 2), -1))
    # followed with its own picks the reference returns the same rows
    rows, need = ref.follow_at(MODEL, params, toks, [43], own)
    np.testing.assert_array_equal(rows, want[43:])
    assert float(need.max()) == 0.0


@pytest.mark.parametrize("attn_impl", ["jnp", "pallas"])
def test_a_key_wider_than_a_lane_row_takes_whole_rows_in_the_pools(attn_impl, monkeypatch):
    """Keys of 160 (the published 192 in small) lie in the pools at 256, zeros
    behind them, so that the device lays a pool out token-major; queries are
    padded alike and the scores are what they were: a chunk and two decode
    steps are the reference's."""
    _interpreted_kernels(monkeypatch)
    c = C.with_(head_dim_override=160, v_head_dim=128, n_layers=2, layer_pattern=(0, 1))
    assert (c.key_pool_dim, C.key_pool_dim, get_config("mimo-v2-flash").key_pool_dim) == (256, 24, 256)
    params = _params(3, c=c)
    kp, vp = make_kv_pool(c, 8, PS, jnp.float32)
    wp = mimo.make_window_pool(c, 8, PS, jnp.float32)
    assert kp.shape[-1] == wp["k"].shape[-1] == 256 and vp.shape[-1] == wp["v"].shape[-1] == 128
    assert mimo.window_page_bytes(c, PS, 4) == 1 * PS * 2 * (256 + 128) * 4
    toks = _tokens(22, 8)
    want = ref.logprobs_at(_model(c), params, toks, [19, 20, 21])
    pt = jnp.asarray([[1, 2, 3] + [0] * 5], jnp.int32)
    pos = jnp.where(jnp.arange(24) < 20, jnp.arange(24), -1)[None]
    lg, kp, vp, wp = mimo.forward(
        c, params, jnp.asarray(np.pad(toks[:20], (0, 4))[None]), pos, kp, vp, pt,
        jnp.asarray([20]), last_index=jnp.int32(19), state=wp, slots=pt + 3,
        attn_impl=attn_impl)
    assert np.abs(_logp(lg[0, 0]) - want[0]).max() < TOL
    for p in (20, 21):
        lg, kp, vp, wp = mimo.forward(
            c, params, jnp.asarray([[toks[p]]]), jnp.asarray([[p]]), kp, vp, pt,
            jnp.asarray([p + 1]), state=wp, slots=pt + 3, attn_impl=attn_impl)
        assert np.abs(_logp(lg[0, 0]) - want[p - 19]).max() < TOL
    assert float(jnp.abs(kp[..., 160:]).max()) == 0.0 and float(jnp.abs(kp[..., :160]).max()) > 0


def test_a_stale_window_table_entry_fails(params):
    """The poisoning has teeth: a LIVE entry that points at a page somebody
    else wrote moves the logits; a freed one does not."""
    toks = _tokens(30, 3)
    t = _Tables(range(1, 5), range(1, 10))
    lg, (kp, vp, wp) = _chunk(params, _pools(), t, toks, 0, 29)

    def step(wpool, wpages):
        t.cover(29, 29)
        pt, _ = t.rows()
        wpt = jnp.asarray([list(wpages) + [0] * (MP - len(wpages))], jnp.int32)
        return mimo.forward(C, params, jnp.asarray([[toks[29]]]), jnp.asarray([[29]]),
                            kp, vp, pt, jnp.asarray([30]), state=wpool, slots=wpt)[0][0, 0]

    t.cover(29, 29)
    good = step(wp, t.wpages)
    assert t.wpages[0] == 0 and t.freed  # logical page 0 left the window
    np.testing.assert_array_equal(np.asarray(step(_poison(wp, t.freed), t.wpages)),
                                  np.asarray(good))
    live = [w for w in t.wpages if w]
    assert np.abs(np.asarray(step(_poison(wp, live[:1]), t.wpages) - good)).max() > 1e-2


def test_the_shares_add_up(params):
    """The held parts of all shares, what every chip computes alike counted
    once, are the uncut layer: the reference at 4 held from 0 and from 4
    differ from the whole layer's output by exactly the other share's part,
    so (share A - common) + (share B - common) + common is the whole, where
    common is the residual stream without any expert (0 held is not a
    configuration, so it is read as A + B - whole)."""
    toks = _tokens(20, 7)
    cut = lambda first: {**MODEL, "n_experts_held": 4, "expert_first": first}
    share = lambda first: {**params, "layers": {
        k: (v[:, first:first + 4] if k in ("we_gate", "we_up", "we_down") else v)
        for k, v in params["layers"].items()}}

    def one_layer(model, tree):
        """The first expert layer's own contribution to the stream."""
        one = {**model, "n_layers": 2, "layer_pattern": [0, 1]}
        t = {**tree, "layers": jax.tree.map(lambda a: a[:1], tree["layers"])}
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.hidden_states(one, t, toks)[0])

    whole = one_layer(MODEL, params)
    a, b = one_layer(cut(0), share(0)), one_layer(cut(4), share(4))
    # the program's own held share agrees with the reference's
    c_a = C.with_(n_experts_held=4, expert_first=0, n_layers=2, layer_pattern=(0, 1))
    lg = _program_stream(c_a, {**share(0), "layers": jax.tree.map(
        lambda x: x[:1], share(0)["layers"]),
        "attn_window": jax.tree.map(lambda x: x[:1], params["attn_window"]),
        "attn_global": jax.tree.map(lambda x: x[:1], params["attn_global"])}, toks)
    # expert outputs are additive over the shares; attention and the dense
    # layer are in all three once
    no_experts = a + b - whole
    np.testing.assert_allclose((a - no_experts) + (b - no_experts) + no_experts, whole,
                               atol=1e-5)
    assert np.abs(a - whole).max() > 1e-3 and np.abs(b - whole).max() > 1e-3
    want = ref.logprobs_at({**cut(0), "n_layers": 2, "layer_pattern": [0, 1]}, {
        **share(0), "layers": jax.tree.map(lambda x: x[:1], share(0)["layers"])}, toks, [19])
    assert np.abs(_logp(lg) - want[0]).max() < TOL


def _program_stream(c, tree, toks):
    n = len(toks)
    kp, vp = make_kv_pool(c, 8, PS, jnp.float32)
    wp = mimo.make_window_pool(c, 8, PS, jnp.float32)
    pt = jnp.arange(1, MP + 1, dtype=jnp.int32)[None] % 8
    return mimo.forward(c, tree, jnp.asarray(toks[None]), jnp.arange(n)[None], kp, vp, pt,
                        jnp.asarray([n]), last_index=jnp.int32(n - 1), state=wp, slots=pt)[0][0, 0]


# -- the kernels, interpreted, against their jnp forms ---------------------------


@pytest.mark.parametrize("Hk,G,window,sinked", [
    (4, 2, None, False), (8, 1, 16, True), (4, 2, 16, True), (8, 2, None, True),
    # the cell's two kinds of layer (Hk 4, G 16 global; Hk 8, G 8 under the
    # window with the sink), G 3, one KV head, and a traced window of 0:
    # global at run time
    (4, 16, None, False), (8, 8, 16, True), (2, 3, 16, True), (1, 4, 16, True),
    (2, 16, 0, True)])
def test_attention_kernels_with_two_head_sizes_and_a_sink(Hk, G, window, sinked):
    """decode, ragged and flash-prefill at dk 24 != dv 16, with and without
    the sink, Hk 1 to 8, windowed and global, against paged_attention_jnp.
    The decode kernel takes the tile routine at every one of them."""
    rs = np.random.RandomState(Hk * 10 + G)
    dk, dv, NP, L, B = 24, 16, 40, 2, 3
    kp = jnp.asarray(rs.randn(L, NP, PS, Hk, dk), jnp.float32)
    vp = jnp.asarray(rs.randn(L, NP, PS, Hk, dv), jnp.float32)
    sink = jnp.asarray(rs.randn(Hk, G), jnp.float32) if sinked else None
    w = None if window is None else jnp.int32(window)
    pt = jnp.asarray(rs.permutation(np.arange(1, NP))[:B * 6].reshape(B, 6), jnp.int32)
    kvl = jnp.asarray([37, 5, 0], jnp.int32)
    q = jnp.asarray(rs.randn(B, Hk, G, dk), jnp.float32)
    got = decode_paged_attention(q, kp, vp, pt, kvl, w, jnp.int32(1), sink=sink,
                                 name=None if window is None else "window_attention_decode",
                                 interpret=True)
    want = paged_attention_jnp(q[:, None], kp[1], vp[1], pt,
                               jnp.maximum(kvl - 1, 0)[:, None], kvl, window=w, sink=sink)[:, 0]
    assert got.shape == (B, Hk, G, dv)
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-6)
    assert float(jnp.abs(got[2]).max()) == 0.0
    S = 16
    qs, ql = jnp.asarray([20, 0, 3], jnp.int32), jnp.asarray([16, 9, 1], jnp.int32)
    q5 = jnp.asarray(rs.randn(B, S, Hk, G, dk), jnp.float32)
    got = prefill_paged_attention(q5, kp, vp, pt, qs, ql, qs + ql, w, jnp.int32(0),
                                  sink=sink, q_block=8, interpret=True)
    want = paged_attention_jnp(q5, kp[0], vp[0], pt, qs[:, None] + jnp.arange(S)[None],
                               qs + ql, window=w, sink=sink)
    valid = (jnp.arange(S)[None] < ql[:, None])[:, :, None, None, None]
    np.testing.assert_allclose(jnp.where(valid, got, 0), jnp.where(valid, want, 0), atol=2e-6)
    q_lens, starts, kvls = [1, 1, 10, 5], [36, 4, 13, 0], [37, 5, 23, 5]
    rows = [list(np.asarray(r)) for r in pt] + [list(rs.permutation(np.arange(1, NP))[:6])]
    md = build_ragged_metadata(q_lens, starts, kvls, rows, 24, max_pages=6)
    qf = jnp.asarray(rs.randn(24, Hk, G, dk), jnp.float32)
    got = ragged_paged_attention(
        qf, kp, vp, *(jnp.asarray(md[k]) for k in ("seg_page_table", "seg_kv_lens", "meta")),
        w, jnp.int32(1), sink=sink, interpret=True)
    want = paged_attention_jnp(
        qf[:, None], kp[1], vp[1], jnp.asarray(md["tok_page_table"]),
        jnp.maximum(jnp.asarray(md["tok_positions"]), 0)[:, None],
        jnp.asarray(md["tok_kv_lens"]), window=w, sink=sink)[:, 0]
    real = (jnp.asarray(md["tok_positions"]) >= 0)[:, None, None, None]
    np.testing.assert_allclose(jnp.where(real, got, 0), jnp.where(real, want, 0), atol=2e-6)


# -- the scheduler's two kinds of pages ------------------------------------------


def _sched(window_pages=12, **kw):
    side = WindowPages(window_pages, PS, W)
    kw = {"max_batch": 3, "chunk_size": 16, "decode_steps": 2, "mixed_prefill_tokens": 8,
          "mixed_prefill_seqs": 2, "mixed_min_chunk": 4, "enable_prefix_cache": False, **kw}
    return Scheduler(PagePool(64, PS), side=side, **kw), side.pool


def _seq(rid, n, max_tokens=30):
    return Sequence(request_id=rid, prompt=list(range(1, n + 1)), sampling={},
                    stop={"max_tokens": max_tokens})


def test_the_scheduler_accounts_for_both_kinds_of_pages():
    sched, wp = _sched()
    a = _seq("a", 40)
    sched.add(a)
    start = 0
    while a.state != SeqState.RUNNING:
        plan = sched.step_plan()
        assert isinstance(plan, PrefillPlan) and plan.start_pos == start
        lo = mimo.window_first_live_page(start, W, PS)
        hi = (start + len(plan.chunk) - 1) // PS
        # every page the chunk's queries see is there, nothing below is kept
        assert all(a.side[j] for j in range(lo, hi + 1))
        assert not any(a.side[:lo]) and a.side.lo == lo
        sched.complete_prefill(plan)
        start += len(plan.chunk)
    assert len(a.pages) == 6  # the global table keeps all
    sched.complete_decode(a, 7, advance_computed=False)
    used = []
    for _ in range(12):
        plan = sched.step_plan()
        for _ in range(plan.n_steps):
            sched.complete_decode(a, 7)
        used.append(wp.num_pages - 1 - wp.n_free)
        assert sum(1 for w in a.side if w) == used[-1]
    # a decode row in its steady state: one page back for each one taken
    assert max(used) <= mimo.window_pages_needed(W, PS, 2) and sched.side.freed >= 5
    assert sched.side.tokens_resident(sched.active) <= used[-1] * PS < a.computed_len
    b = _seq("b", 9)
    sched.add(b)
    sched.step_plan()
    assert b.side and any(b.side)
    held = [w for w in a.side if w]
    sched._preempt(a)
    assert a.side is None and a.computed_len == 0
    assert all(p in wp.free for p in held)
    sched.abort("b")
    sched.abort("a")
    assert wp.n_free == wp.num_pages - 1 and sched.pool.n_free == 64


def test_an_admission_waits_for_a_window_page_and_says_so():
    sched, wp = _sched(window_pages=3, chunk_size=8)  # 2 pages: a's and b's first
    a, b, c = _seq("a", 12), _seq("b", 12), _seq("c", 12)
    sched.add(a), sched.add(b), sched.add(c)
    plan = sched.step_plan()
    assert plan.seq is a and b.state == SeqState.PREFILL and any(b.side)
    assert c.state == SeqState.WAITING and not c.pages and sched.side.waits == 1
    assert wp.n_free == 0 and sched.pool.n_free == 64 - 4
    with pytest.raises(NoSpace):
        sched.side.cover(b, 0, 11)
    assert sum(1 for w in b.side if w) == 1  # nothing more taken
    with pytest.raises(ValueError, match="matches no prefix"):
        Scheduler(PagePool(8, PS), side=sched.side, enable_prefix_cache=True)
    with pytest.raises(ValueError, match="sliding window"):
        Scheduler(PagePool(8, PS), side=WindowPages(3, PS, 0), enable_prefix_cache=False)


# -- the engine --------------------------------------------------------------------


def _engine(monkeypatch, params, **engine_kw):
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    args = worker.parse_args([
        "--model", "tiny-mimo", "--max-batch", "4", "--chunk-size", "16",
        "--mixed-prefill-tokens", "12", "--mixed-prefill-seqs", "2",
        "--mixed-min-chunk", "4"])
    runner = ModelRunner(
        C, num_pages=96, page_size=PS, max_pages_per_seq=16, decode_buckets=(2, 4),
        prefill_buckets=(8, 16), ragged_buckets=(8, 16), params=params,
        dtype=jnp.float32)
    for k, v in engine_kw.items():
        setattr(args, k, v)
    engine, _ = worker.build_engine(args, runner=runner)
    engine.scheduler.decode_steps = 2
    return engine, runner


async def _serve(engine, ids, n_out, cancel_after=None, logprobs=True):
    toks, lps = [], []
    payload = {"token_ids": [int(t) for t in ids],
               "sampling": {"temperature": 0.0, **({"logprobs": 0} if logprobs else {})},
               "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}}
    async for item in engine.generate(payload, Context()):
        toks += list(item.get("token_ids") or [])
        lps += [e["logprob"] for e in item.get("logprobs") or []]
        if cancel_after is not None and len(toks) >= cancel_after:
            return toks, lps  # leaving the stream aborts the request
        if item.get("finish_reason"):
            assert item["finish_reason"] != "error", item
            break
    return toks, lps


def _held_to_reference(params, ids, toks, lps):
    seq = np.asarray(list(ids) + toks[:-1], np.int32)
    at = list(range(len(ids) - 1, len(seq)))
    want = ref.logprobs_at(MODEL, params, seq, at)
    assert np.abs(want[np.arange(len(toks)), toks] - np.asarray(lps)).max() < TOL
    assert float((want.max(-1) - want[np.arange(len(toks)), toks]).max()) < TOL


async def test_engine_sizes_the_window_pool_and_serves_through_every_program(monkeypatch, params):
    engine, runner = _engine(monkeypatch, params)
    try:
        sched, wp = engine.scheduler, engine.side.pool
        assert runner.side_kind == "window" and runner.ragged_mixed
        assert isinstance(runner, Runner) and Runner.side_kind is None
        # 4 rows x the pages 2 fused steps see, one iteration's chunks, scratch
        a_row = mimo.window_pages_needed(W, PS, 4)  # (sized at the built decode_steps)
        assert runner.side_units == wp.num_pages >= 1 + 4 * a_row
        assert runner.side_unit_bytes == mimo.window_page_bytes(C, PS, 4)
        assert runner.k_pool.shape == (2, 96, PS, 1, 24) and runner.v_pool.shape[-1] == 16
        assert runner.state["k"].shape == (5, wp.num_pages, PS, 2, 24)
        assert not sched.enable_prefix_cache and wp.n_free == wp.num_pages - 1
        # junk in every window page: nothing a sequence reads before it wrote it
        runner.state = jax.tree.map(lambda a: a + 9.0, runner.state)
        lead = _tokens(12, 10)
        rest = [_tokens(n, 11 + n) for n in (19, 26, 45)]

        async def late(ids, **kw):
            await asyncio.sleep(0.05)
            return await _serve(engine, ids, 5, **kw)

        got = await asyncio.gather(_serve(engine, lead, 40), *(late(r) for r in rest))
        for ids, (toks, lps) in zip([lead] + rest, got):
            _held_to_reference(params, ids, toks, lps)
        recs = engine.recorder.snapshot()
        assert all(r.window_pages_total == wp.num_pages - 1 for r in recs)
        assert max(r.window_pages_used for r in recs) >= 4
        assert sched.side.freed > 0  # (/metrics' window_pages_freed_total)
        assert all(r.window_tokens_resident <= r.context_tokens_live for r in recs)
        assert any(0 < r.window_tokens_resident < r.context_tokens_live for r in recs)
        assert any(0 < r.decode_pages_live_window < r.decode_pages_live_global for r in recs)
        assert wp.n_free == wp.num_pages - 1 and engine.pool.n_free == 96
        # without logprobs the same drive rides the ragged program
        calls0 = runner.compile_stats()["ragged"]["calls"]
        outs = await asyncio.gather(_serve(engine, lead, 40, logprobs=False),
                                    *(late(r, logprobs=False) for r in rest))
        assert runner.compile_stats()["ragged"]["calls"] > calls0
        assert [o[0] for o in outs] == [g[0] for g in got]
        load = [r for r in engine.recorder.snapshot() if r.moe_experts_hit]
        assert load and all(r.moe_held_slots <= r.moe_token_slots for r in load)
    finally:
        engine.stop()


async def test_preempted_and_cancelled_sequences_leave_no_window_page_behind(monkeypatch, params):
    """A sequence preempted mid-decode gives both kinds of pages back and,
    readmitted, prefills again from position 0; one cancelled mid-decode
    frees its pages, and the next sequence takes those very window pages
    (stale keys in them). Both end with the logprobs of a fresh run."""
    engine, runner = _engine(monkeypatch, params)
    try:
        sched, wp = engine.scheduler, engine.side.pool
        a, b = _tokens(30, 20), _tokens(21, 21)
        plan, seen = sched.step_plan, {}

        def preempting():
            run = [s for s in sched.active if s.state == SeqState.RUNNING]
            if run and run[0].n_generated >= 4 and not seen:
                held = [w for w in run[0].side if w]
                # (StepsInFlight while the loop has a dispatch in flight:
                # it commits that and plans again, and this runs again)
                sched._preempt(run[0])
                seen["held"] = held
            return plan()

        sched.step_plan = preempting
        toks, lps = await _serve(engine, a, 12)
        assert seen["held"] and len(toks) == 12
        _held_to_reference(params, a, toks, lps)
        sched.step_plan = plan
        await _serve(engine, a, 30, cancel_after=6)
        for _ in range(200):
            if not sched.active:
                break
            await asyncio.sleep(0.01)
        assert wp.n_free == wp.num_pages - 1 and engine.pool.n_free == 96
        freed = set(wp.free[-3:])
        assert float(jnp.abs(runner.state["k"][:, jnp.asarray(sorted(freed))]).max()) > 0

        async def watch():
            while not (sched.active and any(sched.active[0].side or ())):
                await asyncio.sleep(0.001)
            return set(w for w in sched.active[0].side if w)

        took, (toks, lps) = await asyncio.gather(watch(), _serve(engine, b, 8))
        assert took & freed
        _held_to_reference(params, b, toks, lps)
    finally:
        engine.stop()


def test_a_step_on_a_window_pool_nobody_sized_raises(params):
    runner = ModelRunner(C, num_pages=16, page_size=PS, max_pages_per_seq=8, params=params,
                         dtype=jnp.float32)
    with pytest.raises(RuntimeError, match="ensure_side_cache"):
        runner.prefill([1, 2, 3], 0, [1], prior_len=0)
    assert runner.ensure_side_cache(9) == 9 == runner.side_units
    assert runner.ensure_side_cache(4) == 9  # (it never shrinks)
    # the warm-up's dummies: one table, no window table (every entry scratch)
    runner.prefill([1, 2, 3], 0, [1], prior_len=0)
    assert runner.device_report()["window_pages"] == 9


def test_every_path_that_cannot_carry_a_window_pool_refuses_in_words(monkeypatch, params):
    words = "window pool"
    assert words in WindowPages.no_prefix and words in refusal("window", "m", "x")
    with pytest.raises(ValueError, match="tier demotion.*" + words):
        _engine(monkeypatch, params, host_kv_blocks=8)
    with pytest.raises(ValueError, match="speculative decoding.*" + words):
        _engine(monkeypatch, params, spec_ngram=True)
    with pytest.raises(NotImplementedError, match="quantized KV cache.*" + words):
        ModelRunner(C, num_pages=16, page_size=PS, max_pages_per_seq=8, params=params,
                    dtype=jnp.float32, kv_quantize="int8")
    engine, runner = _engine(monkeypatch, params)
    try:
        for call, what in (
                (lambda: runner.export_pages_device([1]), "KV export"),
                (lambda: runner.import_pages_device([1], 0, None, None), "KV import"),
                (lambda: runner.export_pages([1]), "KV export"),
                (lambda: runner.import_pages([1], 0, {}), "KV import"),
                (lambda: runner.verify_spec([1], [0], [[1]], [[2]], {}, 1),
                 "speculative verify")):
            with pytest.raises(NotImplementedError, match=what + ".*" + words):
                call()
        assert not runner.has_verify_spec
        with pytest.raises(ValueError, match="transferred KV"):
            engine.scheduler.admit_with_kv(_seq("d", 2))

        async def ask(**extra):
            items = []
            async for item in engine.generate(
                    {"token_ids": [1, 2, 3], "sampling": {"temperature": 0.0, **extra.pop("sampling", {})},
                     "stop": {"max_tokens": 2}, **extra}, Context()):
                items.append(item)
            return items[-1]

        err = asyncio.run(ask(annotations={"disagg": "prefill"}))
        assert err["finish_reason"] == "error" and "disaggregated" in err["error"] and words in err["error"]
        err = asyncio.run(ask(sampling={"n": 2}))
        assert err["finish_reason"] == "error" and "n > 1" in err["error"] and words in err["error"]
        # no stored-block events: such a worker publishes nothing to match
        asyncio.run(ask())
        assert not engine.pool.events
    finally:
        engine.stop()
