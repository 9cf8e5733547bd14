"""DeepSeek-V3.2's layer (CPU, toy sizes, seeded random weights): the
lightning indexer, its key cache in the pool's second array, and latent
attention over the indexer's `index_topk` best tokens, on every path a
sequence takes, against the benchmark's plain reference
(`benchmark/reference/dsa_moe_decoder.py`), which is pinned to transformers'
`DeepseekV3ForCausalLM` where no selection can occur. ModelConfig fields
`index_topk`, `index_n_heads`, `index_head_dim`; preset `tiny-dsa`."""

import asyncio
import dataclasses
import importlib.util
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models import llama, mla
from dynamo_tpu.models.config import ModelConfig, get_config
from dynamo_tpu.models.moe import _moe_block
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.runtime.context import Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = get_config("tiny-dsa")  # index_topk 8: nearly every query selects
PAGE = 4


def _reference(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, os.path.join(REPO, "benchmark", "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _reference("dsa_moe_decoder")


@pytest.fixture(scope="module")
def params():
    p = llama.init_params(TOY, jax.random.PRNGKey(3), jnp.float32)
    # a LayerNorm that is not the identity: the init fills 1.0 and 0.0
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    for stack, (a, b) in (("layers", k[:2]), ("layers_dense", k[2:])):
        lp = p[stack]
        lp["ik_norm"] = 1.0 + 0.3 * jax.random.normal(a, lp["ik_norm"].shape)
        lp["ik_norm_b"] = 0.2 * jax.random.normal(b, lp["ik_norm_b"].shape)
    return p


def _model(c: ModelConfig) -> dict:
    return dataclasses.asdict(c)


def _logsm(rows):
    return np.asarray(jax.nn.log_softmax(np.concatenate(rows), axis=-1))


_forward = jax.jit(llama.forward, static_argnums=0)  # one compile a shape


def _fwd(c, p, toks, pos, pools, pt, kv_lens):
    with jax.default_matmul_precision("highest"):
        out = _forward(c, p, jnp.asarray(toks), jnp.asarray(pos), pools[0],
                       pools[1], pt, jnp.asarray(kv_lens))
    return np.asarray(out[0]), (out[1], out[2])


# -- (1) reference against program, every path a sequence takes --------------

TOKS = np.random.default_rng(0).integers(1, TOY.vocab_size, 44)


@pytest.mark.parametrize("path", ["one_shot", "chunks_straddle_topk", "decode_past_topk"])
def test_one_sequence_matches_the_reference(ref, params, path):
    """44 tokens at index_topk 8: in one prefill; in chunks of 5, 6 and 33
    (the second straddles position 8, the third runs on a prior context);
    a 6-token prefill (dense: at most index_topk) and 38 decode steps through
    the cache, which pass index_topk at the third."""
    c = TOY
    pt = jnp.arange(1, 13, dtype=jnp.int32)[None, :]
    pools = llama.make_kv_pool(c, 16, PAGE, jnp.float32)
    cuts = {"one_shot": [44], "chunks_straddle_topk": [5, 11, 44],
            "decode_past_topk": [6] + list(range(7, 45))}[path]
    rows, a = [], 0
    for b in cuts:
        out, pools = _fwd(c, params, TOKS[None, a:b], np.arange(a, b)[None], pools, pt, [b])
        rows.append(out[0])
        a = b
    want = ref.logprobs_at(_model(c), params, TOKS, list(range(44)))
    assert np.abs(_logsm(rows) - want).max() < 2e-4
    # and the selection is what moved them: the model without it reads apart
    dense = ref.logprobs_at(_model(c.with_(index_topk=64)), params, TOKS, list(range(44)))
    err = np.abs(dense - want).max(-1)
    assert err[: c.index_topk].max() < 1e-5 and err[c.index_topk + 4:].min() > 1e-3


def test_a_latent_wider_than_a_lane_row_is_cached_in_whole_rows(ref):
    """Latent rank 144 + a rotary key of 16 = 160: the pool holds 256-wide
    rows, zeros behind the key (`mla_pool_dim`; 576 -> 640 at the published
    widths), queries are padded to match, and nothing of the logits moves."""
    c = TOY.with_(kv_lora_rank=144)
    assert (c.mla_cache_dim, c.mla_pool_dim, TOY.mla_pool_dim) == (160, 256, 48)
    assert get_config("deepseek-v3.2").mla_pool_dim == 640
    assert get_config("deepseek-v3").mla_pool_dim == 576  # no indexer: as it was
    p = llama.init_params(c, jax.random.PRNGKey(8), jnp.float32)
    pools = llama.make_kv_pool(c, 16, PAGE, jnp.float32)
    assert pools[0].shape[-1] == 256
    pt = jnp.arange(1, 13, dtype=jnp.int32)[None, :]
    rows, a = [], 0
    for b in [5, 20] + list(range(21, 31)):
        out, pools = _fwd(c, p, TOKS[None, a:b], np.arange(a, b)[None], pools, pt, [b])
        rows.append(out[0])
        a = b
    assert float(jnp.abs(pools[0][..., 160:]).max()) == 0.0
    want = ref.logprobs_at(_model(c), p, TOKS[:30], list(range(30)))
    assert np.abs(_logsm(rows) - want).max() < 2e-4


def test_rows_of_unequal_length_in_one_decode_step(ref, params):
    """Three rows of 5, 17 and 30 cached tokens decode in one step: the first
    attends to all it has (under index_topk), the others select."""
    c = TOY
    lens = [5, 17, 30]
    pools = llama.make_kv_pool(c, 32, PAGE, jnp.float32)
    tables = np.zeros((3, 10), np.int32)
    seqs = [np.random.default_rng(10 + i).integers(1, c.vocab_size, n + 1)
            for i, n in enumerate(lens)]
    for i, n in enumerate(lens):
        tables[i] = np.arange(1 + 10 * i, 11 + 10 * i)
        _, pools = _fwd(c, params, seqs[i][None, :n], np.arange(n)[None], pools,
                        jnp.asarray(tables[i:i + 1]), [n])
    out, _ = _fwd(c, params, np.asarray([[s[-1]] for s in seqs]),
                  np.asarray([[n] for n in lens]), pools, jnp.asarray(tables),
                  [n + 1 for n in lens])
    for i, n in enumerate(lens):
        want = ref.logprobs_at(_model(c), params, seqs[i], [n])[0]
        got = np.asarray(jax.nn.log_softmax(out[i, 0]))
        assert np.abs(got - want).max() < 2e-4, i


def _runner(params, **kw):
    kw.setdefault("num_pages", 64)
    return ModelRunner(
        TOY, page_size=PAGE, max_pages_per_seq=16, decode_buckets=(1, 2, 4),
        prefill_buckets=(8, 16, 32), dtype=jnp.float32, params=params, **kw)


async def _serve(engine, prompts, n_out):
    async def one(ids):
        toks, lps, final = [], [], None
        req = {"token_ids": list(ids), "sampling": {"temperature": 0.0, "logprobs": 0},
               "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}}
        async for item in engine.generate(req, Context()):
            toks += item.get("token_ids") or []
            lps += [e["logprob"] for e in item.get("logprobs") or []]
            if item.get("finish_reason"):
                final = item
                break
        return toks, lps, final

    return await asyncio.gather(*(one(p) for p in prompts))


def _held_to_the_reference(ref, params, ids, toks, lps):
    seq = np.asarray(list(ids) + toks[:-1])
    at = list(range(len(ids) - 1, len(seq)))
    want = ref.logprobs_at(_model(TOY), params, seq, at)[np.arange(len(toks)), toks]
    assert np.abs(want - np.asarray(lps)).max() < 2e-4


def test_a_preempted_sequence_recomputes_to_the_same_logprobs(ref, params):
    """Two requests that cannot both fit the pool: the younger is preempted
    and recomputed (index keys and latents alike), and every served logprob
    is the reference's."""
    engine = InferenceEngine(_runner(params, num_pages=12), max_batch=4, chunk_size=16,
                             enable_prefix_cache=False)
    engine.start()
    prompts = [list(range(3, 15)), list(range(40, 52))]
    try:
        got = asyncio.run(_serve(engine, prompts, 16))
    finally:
        engine.stop()
    assert sorted(f["phases"]["preemptions"] for _, _, f in got) == [0, 1]
    for ids, (toks, lps, _) in zip(prompts, got):
        assert len(toks) == 16
        _held_to_the_reference(ref, params, ids, toks, lps)


def test_a_prefix_cached_by_another_gives_the_same_bits(ref, params):
    """The second and the third request find their first 24 tokens in pages
    the first left: latent pages and index keys under the same page ids. They
    agree bit for bit (the same programs on the same pages), with the first to
    rounding (its prefill was one chunk of 26, theirs 2 on a prior context:
    other shapes, another order of sums), and all with the reference."""
    runner = _runner(params)
    engine = InferenceEngine(runner, max_batch=4, chunk_size=32)
    engine.start()
    ids = list(range(5, 31))  # 26 tokens: six whole pages and two more
    try:
        (t1, l1, _), = asyncio.run(_serve(engine, [ids], 6))
        reused0 = engine.scheduler.reused_prefix_tokens
        (t2, l2, _), = asyncio.run(_serve(engine, [ids], 6))
        (t3, l3, _), = asyncio.run(_serve(engine, [ids], 6))
        reused = engine.scheduler.reused_prefix_tokens - reused0
    finally:
        engine.stop()
    assert reused == 48 and t1 == t2 == t3 and l2 == l3
    np.testing.assert_allclose(l1, l2, atol=1e-5)
    _held_to_the_reference(ref, params, ids, t1, l1)
    recs = [r for r in engine.recorder.snapshot() if r.decode_seqs]
    assert recs and all(0 < r.dsa_sel_tokens < r.dsa_ctx_tokens for r in recs)
    one = recs[-1]
    assert one.dsa_sel_tokens == TOY.index_topk * one.decode_seqs * one.decode_steps


def test_pages_move_as_the_pair(params):
    """What copies or exports a page by its id carries the index keys with the
    latent: copy_pages, and the wire payload of export_pages / import_pages."""
    r = _runner(params)
    r.prefill(list(range(1, 9)), 0, [1, 2], prior_len=0)
    assert r.k_pool.shape[-1] == TOY.mla_cache_dim and r.v_pool.shape[-1] == TOY.index_head_dim
    assert float(jnp.abs(r.v_pool[:, 1:3]).min()) > 0  # every index key written
    r.copy_pages(1, 5)
    np.testing.assert_array_equal(np.asarray(r.v_pool[:, 5]), np.asarray(r.v_pool[:, 1]))
    np.testing.assert_array_equal(np.asarray(r.k_pool[:, 5]), np.asarray(r.k_pool[:, 1]))
    payload = r.export_pages([1, 2])
    assert payload["v_shape"][-1] == TOY.index_head_dim
    r2 = _runner(params)
    r2.import_pages([7, 8], 0, payload)
    np.testing.assert_array_equal(np.asarray(r2.v_pool[:, 7:9]), np.asarray(r.v_pool[:, 1:3]))
    np.testing.assert_array_equal(np.asarray(r2.k_pool[:, 7:9]), np.asarray(r.k_pool[:, 1:3]))


# -- (2) where no selection can occur: transformers' deepseek_v3 -------------


def test_without_a_selection_it_is_transformers_deepseek_v3(ref, tmp_path):
    """index_topk >= the sequence: the reference's logits are
    DeepseekV3ForCausalLM's (float32, a compressed query, group-limited
    sigmoid router, a leading dense layer), and the program with an indexer is
    the program without one, bit for bit."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from safetensors.torch import save_file

    from dynamo_tpu.engine.weights import config_from_hf, load_hf_checkpoint

    kw = dict(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_hidden_layers=3,
        num_attention_heads=2, num_key_value_heads=2, kv_lora_rank=16, q_lora_rank=24,
        qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=24, n_shared_experts=1,
        routed_scaling_factor=2.5, scoring_func="sigmoid", topk_method="noaux_tc",
        norm_topk_prob=True, n_group=4, topk_group=2, first_k_dense_replace=1,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=False)
    torch.manual_seed(3)
    hf = transformers.DeepseekV3ForCausalLM(
        transformers.DeepseekV3Config(**kw, attn_implementation="eager")).eval()
    save_file({k: v.clone().contiguous() for k, v in hf.state_dict().items()},
              str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({"model_type": "deepseek_v3", **kw}))
    plain = config_from_hf(str(tmp_path), name="toy-v3")
    p = jax.tree.map(jnp.asarray, load_hf_checkpoint(str(tmp_path), plain, dtype="float32"))
    toks = np.asarray([3, 9, 27, 41, 5, 11, 60, 2, 17, 33])
    with torch.no_grad():
        want = torch.log_softmax(hf(torch.tensor(toks[None])).logits[0], -1).numpy()

    c = plain.with_(index_topk=16, index_n_heads=2, index_head_dim=8)
    drawn = llama.init_params(c, jax.random.PRNGKey(1), jnp.float32)
    for stack in ("layers", "layers_dense"):
        p[stack].update({k: v for k, v in drawn[stack].items()
                         if k.startswith(("wi_", "ik_"))})
    got = ref.logprobs_at(_model(c), p, toks, list(range(len(toks))))
    assert np.abs(got - want).max() < 2e-4

    pt = jnp.arange(1, 5, dtype=jnp.int32)[None, :]
    with_ix, _ = _fwd(c, p, toks[None], np.arange(10)[None],
                      llama.make_kv_pool(c, 8, PAGE, jnp.float32), pt, [10])
    without, _ = _fwd(plain, p, toks[None], np.arange(10)[None],
                      llama.make_kv_pool(plain, 8, PAGE, jnp.float32), pt, [10])
    assert with_ix.tobytes() == without.tobytes()
    assert np.abs(np.asarray(jax.nn.log_softmax(with_ix[0])) - want).max() < 2e-4


# -- (3) the selection alone -------------------------------------------------


@pytest.mark.parametrize("k", [1, 8, 13])
def test_the_selected_set_is_argsort_of_the_references_scores(ref, k):
    """Drawn index queries, weights and keys with ties planted (every third
    key a copy of its neighbour): the program's top k are numpy's stable
    argsort of the reference's float32 scores, ties towards the lower position."""
    rng = np.random.default_rng(k)
    S, C, hi, di = 6, 40, 4, 16
    qi = rng.standard_normal((S, hi, di)).astype(np.float32)
    w = rng.standard_normal((S, hi)).astype(np.float32)
    keys = rng.standard_normal((C, di)).astype(np.float32)
    keys[2::3] = keys[1::3][: len(keys[2::3])]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.index_scores(jnp.asarray(qi), jnp.asarray(w), jnp.asarray(keys)))
        got = np.asarray(mla.index_scores(jnp.asarray(qi)[None], jnp.asarray(w)[None],
                                          jnp.asarray(keys)[None]))[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (want[:, 1::3][:, :13] == want[:, 2::3]).all()  # the ties are exact
    mine = np.asarray(mla.select_topk(jnp.asarray(want), k))
    theirs = np.argsort(-want, axis=-1, kind="stable")[:, :k]
    assert (mine == theirs).all()
    # the mask a prefill chunk uses (a radix select, no sort) is the same set,
    # with dead positions at -inf and with negative scores among the live
    for scores in (want, np.where(np.arange(C) < 30, want, -np.inf).astype(np.float32)):
        order = np.argsort(-scores, axis=-1, kind="stable")[:, :k]
        as_mask = np.zeros(scores.shape, bool)
        np.put_along_axis(as_mask, order, True, axis=-1)
        assert (np.asarray(mla.topk_mask(jnp.asarray(scores), k)) == as_mask).all()
        idx, with_sort = mla.select_topk(jnp.asarray(scores), k, with_mask=True)
        assert (np.asarray(with_sort) == as_mask).all()  # a decode step's: the sort's own
        assert (np.asarray(idx) == order).all()
    # and the reference's own mask picks the same set among causal positions
    q_pos = jnp.arange(C - S, C)
    mask = np.asarray(ref.chosen(jnp.asarray(want), q_pos, jnp.arange(C), k))
    causal = np.arange(C)[None, :] <= np.asarray(q_pos)[:, None]
    for t in range(S):
        order = [s for s in np.argsort(-want[t], kind="stable") if causal[t, s]][:k]
        assert sorted(np.flatnonzero(mask[t])) == sorted(order)


# -- (3b) the served selection, as a check that follows it is handed it -------


@pytest.mark.parametrize("C", [64, 40, 1152 * 32])
def test_a_chosen_set_goes_out_as_bit_words_and_comes_back(C):
    """pack_chosen on the device, unpack_chosen on the host; the words of
    "every live token" without the mask; and the rows routed_picks() lays
    below the picks, as the reference takes them apart (token s is bit s % 32
    of word s // 32)."""
    from dynamo_tpu.engine.model_runner import _beside_picks

    rng = np.random.default_rng(C)
    mask = rng.random((2, 3, C)) < 0.3
    words = np.asarray(mla.pack_chosen(jnp.asarray(mask)))
    assert words.shape == (2, 3, mla.chosen_words(C)) and words.dtype == np.int32
    assert (mla.unpack_chosen(words, C) == mask).all()
    pos = np.array([[0, 5, -1], [C - 1, 31, 32]], np.int32)
    kv = np.array([4, C], np.int32)
    live = np.asarray(mla.unpack_chosen(np.asarray(
        mla.all_live_chosen(jnp.asarray(pos), jnp.asarray(kv), C)), C))
    want = (np.arange(C) <= pos[..., None]) & (np.arange(C) < kv[:, None, None])
    assert (live == want).all()
    picks = rng.integers(0, 16, (2, 3, 4)).astype(np.int32)  # [L_moe, n, k]
    out = _beside_picks(picks, words, C)  # the layers lead: L = 2 here
    rows = -(-mla.chosen_words(C) // 4)
    assert out.shape == (2 + 2 * rows, 3, 4) and (out[:2] == picks).all()
    flat = np.moveaxis(out[2:].reshape(2, rows, 3, 4), 1, 2).reshape(2, 3, rows * 4)
    bits = np.unpackbits(flat.astype("<i4").view(np.uint8), axis=-1, bitorder="little")
    assert (bits[..., :C].astype(bool) == mask).all() and not bits[..., C:].any()


def test_a_decode_steps_two_arms_choose_and_attend_alike(monkeypatch):
    """A decode step of three rows past index_topk and one under it: the arm
    the chip runs (the select kernel, the chosen rows gathered, the decode
    kernel over them; both interpreted here) against the masked arm, in what
    they attend to and in what they say they chose."""
    import functools

    from dynamo_tpu.ops import dsa_select as sel_ops
    from dynamo_tpu.ops import mla_attention as ops

    monkeypatch.setattr(ops, "decode_mla_attention",
                        functools.partial(ops.decode_mla_attention, interpret=True))
    monkeypatch.setattr(sel_ops, "dsa_select",
                        functools.partial(sel_ops.dsa_select, interpret=True))
    c = TOY
    rng = np.random.default_rng(5)
    B, H, dc, dr, NP, MP = 4, c.n_heads, c.kv_lora_rank, c.qk_rope_head_dim, 40, 8
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    k_pool, ik_pool = f(2, NP, PAGE, 1, c.mla_pool_dim), f(2, NP, PAGE, 1, c.index_head_dim)
    pt = jnp.asarray(rng.permutation(NP)[: B * MP].reshape(B, MP).astype(np.int32))
    kv = jnp.asarray([29, 32, 5, 17], jnp.int32)
    args = (c, k_pool, ik_pool, jnp.int32(1), f(B, 1, H, dc), f(B, 1, H, dr),
            f(B, 1, c.index_n_heads, c.index_head_dim), f(B, 1, c.index_n_heads),
            pt, (kv - 1)[:, None], kv)
    with jax.default_matmul_precision("highest"):
        out = {impl: mla._selected_attention(*args, attn_impl=impl, dc=dc, scale=0.2)
               for impl in ("jnp", "pallas")}
    np.testing.assert_allclose(out["pallas"][0], out["jnp"][0], rtol=2e-5, atol=2e-5)
    assert (np.asarray(out["pallas"][1]) == np.asarray(out["jnp"][1])).all()
    sets = mla.unpack_chosen(np.asarray(out["pallas"][1]), MP * PAGE)[:, 0]
    assert sets.sum(-1).tolist() == [8, 8, 5, 8]
    assert not (sets & (np.arange(MP * PAGE) >= np.asarray(kv)[:, None])).any()


async def _serve_asking(engine, prompts, n_out):
    async def one(ids):
        toks, lps, routed = [], [], []
        req = {"token_ids": list(ids),
               "sampling": {"temperature": 0.0, "logprobs": 0, "routed_experts": True},
               "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}}
        async for item in engine.generate(req, Context()):
            toks += item.get("token_ids") or []
            lps += [e["logprob"] for e in item.get("logprobs") or []]
            r = item.get("routed_experts")
            if r:
                assert r["start"] == len(routed)
                routed += r["ids"]
            if item.get("finish_reason"):
                break
        return toks, lps, np.asarray(routed, np.int32)

    return await asyncio.gather(*(one(p) for p in prompts))


def test_the_served_selection_streams_below_the_picks_and_is_followed(ref, params):
    """`routed_experts` of a model with an indexer: under the expert layers'
    rows, every layer's chosen tokens of every position, prefill chunks and
    decode steps alike. In float32 they are the reference's own choice (need 0 in
    every column, the followed logprobs the unfollowed ones); a set the
    reference's scores would not choose is told apart by its need, and one of
    the wrong size or with a token the position cannot see is no one's."""
    engine = InferenceEngine(_runner(params), max_batch=4, chunk_size=16,
                             enable_prefix_cache=False)
    engine.start()
    prompts = [list(range(3, 40)), list(range(50, 57))]
    try:
        got = asyncio.run(_serve_asking(engine, prompts, 6))
    finally:
        engine.stop()
    model = _model(TOY)
    n_moe, L, k = TOY.n_layers - TOY.n_dense_layers, TOY.n_layers, TOY.n_experts_active
    for ids, (toks, lps, picks) in zip(prompts, got):
        seq = np.asarray(ids + toks[:-1])
        at = list(range(len(ids) - 1, len(seq)))
        rows = -(-mla.chosen_words(16 * PAGE) // k)
        assert picks.shape == (len(seq), n_moe + L * rows, k)
        experts, served = ref.split_served(model, picks, n_moe)
        assert experts.shape == (len(seq), n_moe, k) and served.shape == (L, len(seq), len(seq))
        size = np.minimum(np.arange(len(seq)) + 1, TOY.index_topk)
        assert (served.sum(-1) == size[None]).all()
        logp, need = ref.follow_at(model, params, seq, at, picks)
        assert need.shape == (len(seq), n_moe + L) and need.max() == 0.0
        assert (logp == ref.logprobs_at(model, params, seq, at)).all()
        assert np.abs(logp[np.arange(len(toks)), toks] - np.asarray(lps)).max() < 2e-4
    # the long prompt's served sets, spoilt: the most recent index_topk tokens
    # (a window) in layer 1; one token too many in layer 2 at the last position
    ids, (toks, _, picks) = prompts[0], got[0]
    seq = np.asarray(ids + toks[:-1])
    experts, served = ref.split_served(model, picks, n_moe)
    T = len(seq)
    spoilt = served.copy()
    spoilt[1] = (np.arange(T)[None] <= np.arange(T)[:, None]) & (
        np.arange(T)[None] > np.arange(T)[:, None] - TOY.index_topk)
    spoilt[2, -1, :] = np.arange(T) <= T - 1
    words = np.packbits(spoilt, axis=-1, bitorder="little")
    words = np.pad(words, [(0, 0), (0, 0), (0, -words.shape[-1] % (4 * k))]).view("<i4")
    below = np.moveaxis(words, 0, 1).reshape(T, -1, k)  # [T, L x rows, k]
    _, need = ref.follow_at(model, params, seq, [T - 1], np.concatenate([experts, below], axis=1))
    assert need[:, :n_moe].max() < np.inf and need[:, n_moe].max() == 0.0
    assert 0.0 < need[TOY.index_topk + 4:, n_moe + 1].min() and need[:, n_moe + 1].max() < np.inf
    # (layer 2 reads what layer 1 left, so its other sets need something now)
    assert np.isinf(need[-1, n_moe + 2]) and need[:-1, n_moe + 2].max() < np.inf


# -- (4) the shares add up ---------------------------------------------------


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_whole_layer(ref, params, side):
    """The routed parts the four chips of a unit compute, each over its own
    quarter of the 16 experts under the group-limited router, plus the shared
    expert counted once, are the uncut reference's layer (`mla_moe_decoder`)."""
    whole_ref = _reference("mla_moe_decoder")
    whole = TOY.with_(n_experts_held=0, expert_first=0)
    full = llama.init_params(whole, jax.random.PRNGKey(5), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], full["layers"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 23, TOY.dim), jnp.float32)
    picks = jnp.zeros((23, TOY.n_experts_active), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _, own = whole_ref._experts(x[0], lp, _model(whole), picks, False)
        shared = whole_ref._swiglu(x[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        parts = []
        for q in range(4):
            c = TOY.with_(n_experts_held=4, expert_first=4 * q)
            mine = {k: (v[4 * q: 4 * q + 4] if k.startswith("we_") else v)
                    for k, v in lp.items()}
            if side == "program":
                y, sel, _ = _moe_block(c, mine, x)
                y, sel = y[0], sel[0]
            else:
                y, need, sel = ref._experts(x[0], mine, _model(c), picks, False)
                assert float(need.max()) == 0.0
            assert (np.sort(np.asarray(sel), -1) == np.sort(np.asarray(own), -1)).all()
            parts.append(np.asarray(y - shared))
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_a_flipped_group_is_held_to_the_margin_not_refused(ref):
    """`need_under_groups`: picks from a group the float32 scores narrowly
    banned read the halved difference of the group scores; picks from more
    groups than are kept read inf; the reference's own read 0."""
    model = {"n_expert_groups": 4, "topk_groups": 2}
    biased = jnp.asarray([[.9, .8, .1, .1, .7, .6, .1, .1, .65, .63, .1, .1, .2, .1, .1, .1]])
    need = lambda picks: float(ref.need_under_groups(biased, jnp.asarray([picks]), model)[0])
    assert need([0, 1, 4, 5]) == 0.0
    # group 2 (1.28) used instead of group 1 (1.30): (1.30 - 1.28) / 2; then
    # among groups 0 and 2 the picks are the top four
    assert need([0, 1, 8, 9]) == pytest.approx(0.01, abs=1e-6)
    assert need([0, 4, 8, 12]) == float("inf")


# -- (5) the loader ----------------------------------------------------------


def _v32_checkpoint(tmp_path, extra=None, mtp=True):
    from safetensors.numpy import save_file

    V, E, L, H, dc, dr, dn, dv, qr, F, MF, NEXP, hi, di = 64, 32, 2, 2, 16, 8, 8, 16, 24, 48, 24, 4, 2, 8
    rng = np.random.default_rng(5)
    w = lambda *shape: rng.standard_normal(shape).astype(np.float32) * 0.05
    t = {"model.embed_tokens.weight": w(V, E), "model.norm.weight": np.ones(E, np.float32),
         "lm_head.weight": w(V, E)}
    for i in range(L + int(mtp)):
        pre = f"model.layers.{i}."
        t[pre + "input_layernorm.weight"] = np.ones(E, np.float32)
        t[pre + "post_attention_layernorm.weight"] = np.ones(E, np.float32)
        t[pre + "self_attn.q_a_proj.weight"] = w(qr, E)
        t[pre + "self_attn.q_a_layernorm.weight"] = np.ones(qr, np.float32)
        t[pre + "self_attn.q_b_proj.weight"] = w(H * (dn + dr), qr)
        t[pre + "self_attn.kv_a_proj_with_mqa.weight"] = w(dc + dr, E)
        t[pre + "self_attn.kv_a_layernorm.weight"] = np.ones(dc, np.float32)
        t[pre + "self_attn.kv_b_proj.weight"] = w(H * (dn + dv), dc)
        t[pre + "self_attn.o_proj.weight"] = w(E, H * dv)
        t[pre + "self_attn.indexer.wq_b.weight"] = w(hi * di, qr)
        t[pre + "self_attn.indexer.wk.weight"] = w(di, E)
        t[pre + "self_attn.indexer.k_norm.weight"] = 1 + w(di)
        t[pre + "self_attn.indexer.k_norm.bias"] = w(di)
        t[pre + "self_attn.indexer.weights_proj.weight"] = w(hi, E)
        if i == 0:
            for part, shape in (("gate", (F, E)), ("up", (F, E)), ("down", (E, F))):
                t[pre + f"mlp.{part}_proj.weight"] = w(*shape)
            continue
        t[pre + "mlp.gate.weight"] = w(NEXP, E)
        t[pre + "mlp.gate.e_score_correction_bias"] = w(NEXP)
        for e in range(NEXP):
            for part, shape in (("gate", (MF, E)), ("up", (MF, E)), ("down", (E, MF))):
                t[pre + f"mlp.experts.{e}.{part}_proj.weight"] = w(*shape)
        for part, shape in (("gate", (MF, E)), ("up", (MF, E)), ("down", (E, MF))):
            t[pre + f"mlp.shared_experts.{part}_proj.weight"] = w(*shape)
    if mtp:  # what layer 61 of the published checkpoint adds to a layer
        t[f"model.layers.{L}.eh_proj.weight"] = w(E, 2 * E)
        t[f"model.layers.{L}.enorm.weight"] = np.ones(E, np.float32)
    t.update(extra or {})
    save_file(t, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps({
        "model_type": "deepseek_v32", "vocab_size": V, "hidden_size": E, "num_hidden_layers": L,
        "num_attention_heads": H, "intermediate_size": F, "kv_lora_rank": dc, "q_lora_rank": qr,
        "qk_rope_head_dim": dr, "qk_nope_head_dim": dn, "v_head_dim": dv,
        "index_topk": 4, "index_n_heads": hi, "index_head_dim": di,
        "n_routed_experts": NEXP, "num_experts_per_tok": 2, "moe_intermediate_size": MF,
        "n_shared_experts": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "routed_scaling_factor": 2.5, "first_k_dense_replace": 1, "n_group": 2, "topk_group": 1,
        "num_nextn_predict_layers": 1, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 64}))
    return t


def test_a_deepseek_v32_checkpoint_round_trips(tmp_path, caplog):
    from dynamo_tpu.engine.weights import config_from_hf, load_hf_checkpoint

    t = _v32_checkpoint(tmp_path)
    c = config_from_hf(str(tmp_path), name="toy-v32")
    assert c.has_indexer and (c.index_topk, c.index_n_heads, c.index_head_dim) == (4, 2, 8)
    assert c.n_dense_layers == 1 and c.n_expert_groups == 2 and c.n_layers == 2
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.engine.weights"):
        p = load_hf_checkpoint(str(tmp_path), c, dtype="float32")
    assert "multi-token-prediction module" in caplog.text and "layer 2" in caplog.text
    shapes = jax.eval_shape(lambda: llama.init_params(c, jax.random.PRNGKey(0), jnp.float32))
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(lambda a: a.shape, shapes)
    ix = "model.layers.1.self_attn.indexer."
    np.testing.assert_array_equal(p["layers"]["wi_q"][0], t[ix + "wq_b.weight"].T)
    np.testing.assert_array_equal(p["layers"]["wi_k"][0], t[ix + "wk.weight"].T)  # no permutation
    np.testing.assert_array_equal(p["layers"]["wi_w"][0], t[ix + "weights_proj.weight"].T)
    np.testing.assert_array_equal(p["layers"]["ik_norm_b"][0], t[ix + "k_norm.bias"])
    np.testing.assert_array_equal(p["layers_dense"]["ik_norm"][0],
                                  t["model.layers.0.self_attn.indexer.k_norm.weight"])
    pools = llama.make_kv_pool(c, 8, 4, jnp.float32)
    out, _ = _fwd(c, jax.tree.map(jnp.asarray, p), [[1, 2, 3, 4, 5, 6, 7]],
                  [list(range(7))], pools, jnp.arange(8, dtype=jnp.int32)[None], [7])
    assert np.isfinite(out).all()


def test_a_tensor_without_a_name_is_refused_in_words(tmp_path):
    from dynamo_tpu.engine.weights import config_from_hf, load_hf_checkpoint

    _v32_checkpoint(tmp_path, extra={
        "model.layers.1.self_attn.indexer.wq_b.weight_scale_inv": np.ones((2, 2), np.float32)})
    c = config_from_hf(str(tmp_path), name="toy-v32")
    with pytest.raises(ValueError, match=r"no name for.*wq_b\.weight_scale_inv"):
        load_hf_checkpoint(str(tmp_path), c, dtype="float32")


# -- (6) config, pool and the refusals on the Runner door --------------------


def test_the_pool_is_two_arrays_under_one_page_table():
    k, v = llama.make_kv_pool(TOY, 8, 4)
    assert k.shape == (3, 8, 4, 1, TOY.mla_cache_dim) and v.shape == (3, 8, 4, 1, 16)
    plain_v = llama.make_kv_pool(get_config("tiny-mla-q"), 8, 4)[1]
    assert plain_v.shape[-1] == 1  # a model without an indexer keeps the stub
    shapes = jax.eval_shape(lambda: llama.init_params(TOY, jax.random.PRNGKey(0)))
    for stack, n in (("layers", 2), ("layers_dense", 1)):
        assert shapes[stack]["wi_q"].shape == (n, 48, 64)
        assert shapes[stack]["wi_k"].shape == (n, TOY.dim, 16)
        assert shapes[stack]["wi_w"].shape == (n, TOY.dim, 4)
        assert shapes[stack]["ik_norm_b"].shape == (n, 16)
    assert "wi_q" not in jax.eval_shape(
        lambda: llama.init_params(get_config("tiny-mla-q"), jax.random.PRNGKey(0)))["layers"]


@pytest.mark.parametrize("bad", [
    dict(attn_type="gqa", v_head_dim=0), dict(q_lora_rank=0), dict(index_n_heads=0),
    dict(index_head_dim=8), dict(index_topk=0)])
def test_an_indexer_off_compressed_latent_attention_is_refused(bad):
    with pytest.raises(ValueError, match="index_topk|latent"):
        TOY.with_(**bad)


def test_the_published_preset_and_the_cells_share_of_it():
    want = get_config("deepseek-v3.2")
    assert (want.index_topk, want.index_n_heads, want.index_head_dim) == (2048, 64, 128)
    v3 = get_config("deepseek-v3")
    assert want == v3.with_(name="deepseek-v3.2", index_topk=2048, index_n_heads=64,
                            index_head_dim=128)
    with open(os.path.join(REPO, "benchmark", "configs", "deepseek-v3.2.json")) as f:
        cfg = json.load(f)
    cell = ModelConfig(**cfg["model"])
    assert cell == want.with_(n_layers=5, n_dense_layers=1, vocab_size=16160, n_experts_held=8,
                              expert_first=8, max_seq_len=36864)
    assert cfg["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 129280}
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    shapes = jax.eval_shape(lambda: llama.init_params(cell, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n / 3226e6 - 1) < 1e-3, n


@pytest.mark.parametrize("kw, what", [
    (dict(kv_quantize="int8"), "quantized KV cache"),
    (dict(mesh_config=MeshConfig(model=2)), "mesh of several devices"),
    (dict(draft_config=get_config("tiny")), "draft model"),
])
def test_the_runner_refuses_in_words(kw, what):
    mesh = kw.pop("mesh_config", None)
    with pytest.raises(NotImplementedError, match=f"{what}.*model with an indexer.*tiny-dsa"):
        ModelRunner(TOY, mesh, num_pages=16, page_size=4, max_pages_per_seq=4, **kw)


def test_no_fused_mixed_program_on_any_platform(params, monkeypatch):
    """`Runner.fuses_mixed` is False: the engine co-schedules chunks with the
    decoding rows as two dispatches even where fusing was asked for, and
    `can_fuse` agrees."""
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    runner = _runner(params)
    engine = InferenceEngine(runner, max_batch=4, chunk_size=16, mixed_prefill_tokens=8)
    assert not runner.fuses_mixed and not engine.fused_mixed
    assert not runner.can_fuse(2, 1, constrained=False)
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    plain = ModelRunner(get_config("tiny-mla-q"), num_pages=16, page_size=4, max_pages_per_seq=4)
    assert plain.fuses_mixed and InferenceEngine(plain, max_batch=2).fused_mixed
