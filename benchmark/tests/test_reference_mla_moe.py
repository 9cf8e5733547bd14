"""reference/mla_moe_decoder.py against the program's forward (prefill, then
decode one token at a time through the paged latent pool) on seeded random
weights at toy sizes, logits and not tokens, and serve.py's check of a routed
model on top of it.

In float32 the two agree to rounding (7e-6 read; limit 1e-4): the mathematics
is the same, router bias, groups, query compression and yarn included, with a
leading dense layer and without one. In bfloat16, as served, the program now
and then picks another expert than float32 arithmetic does (its router input
is rounded), and what it then computes is right for ITS picks and 0.8-3.9 from
what the reference computes for its own (seeds 3, 6, 7, 10, 12, 15-17 of
twenty). So the reference FOLLOWS the served picks (`follow_at`): it computes
every expert layer of every position with the experts the program picked,
after holding each pick against its own float32 selection scores (`need`: how
far they would have to move for the served set to be their top k). Followed,
the two agree to what bf16 activations at a width of 64 allow at EVERY
position (worst 0.114, mean 0.058 over twenty seeds x 40 positions; limits
0.25 and 0.08), and the largest need read is 0.0035 here and 0.0153 through
an engine (4 experts, 2 a token, 2 expert layers; forty seeds of serve.py's
check), under the fixture's `correct_routing_margin` of 2^-5. Nothing is
left out. A pick the scores did not nearly make is inadmissible and fails
the check whatever the logprobs say; a wrong expert matrix passes
admissibility (the picks are the router's) and fails on the logprobs.

The margin is a reading at these sizes. At published widths: PERF.md section
6, PR 32, and the rule in benchmark/README.md."""

import asyncio
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S, N_PREFILL, PAGE = 40, 29, 4


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


serve = _load("serve.py", "bench_serve")
ref = _load(os.path.join("reference", "mla_moe_decoder.py"), "bench_reference_mla_moe")
dense_ref = _load(os.path.join("reference", "dense_decoder.py"), "bench_reference_dense")
with open(os.path.join(BENCH, "tests", "data", "fixture-mla-moe.json")) as _f:
    _REHEARSE = json.load(_f)["rehearse"]
MARGIN, TOL = _REHEARSE["correct_routing_margin"], _REHEARSE["correct_tolerance"]

CONFIGS = {
    "tiny-mla-moe": PRESETS["tiny-mla-moe"].with_(n_shared_experts=2),
    "tiny-mla-q": PRESETS["tiny-mla-q"],
    "grouped": PRESETS["tiny-mla-moe"].with_(n_experts=8, n_expert_groups=4, topk_groups=2),
    "softmax-unnormed": PRESETS["tiny-mla-moe"].with_(
        moe_scoring="softmax", moe_norm_topk=False, moe_router_bias=False, moe_routed_scale=1.0),
    "yarn": PRESETS["tiny-mla-q"].with_(rope_scaling="yarn", rope_factor=4.0, rope_orig_max_seq=16,
                                        rope_mscale=1.0, rope_mscale_all_dim=0.8),
    # every layer routed: the tree has no `layers_dense` stack
    "no-dense-layer": PRESETS["tiny-mla-moe"].with_(n_dense_layers=0, n_layers=2),
}


def _program(c, params, toks):
    """(log-softmax rows [S, V], the router's picks [S, L_moe, k] or None): a
    prefill of N_PREFILL tokens, then decode, as the step programs run it."""
    routed = bool(c.is_moe)
    fwd = jax.jit(lambda *a: llama.forward(c, params, *a, return_routed=routed))
    pages = -(-S // PAGE)
    k, v = llama.make_kv_pool(c, pages + 1, PAGE, dtype=params["embed"].dtype)
    table = jnp.arange(pages, dtype=jnp.int32)[None, :]
    out = fwd(jnp.asarray([toks[:N_PREFILL]]), jnp.arange(N_PREFILL)[None, :], k, v,
              table, jnp.asarray([N_PREFILL]))
    rows, picks = [out[0][0]], [out[3][:, 0]] if routed else []
    for t in range(N_PREFILL, S):
        out = fwd(jnp.asarray([[toks[t]]]), jnp.asarray([[t]]), out[1], out[2], table,
                  jnp.asarray([t + 1]))
        rows.append(out[0][0])
        if routed:
            picks.append(out[3][:, 0])  # [L_moe, 1, k]
    logp = np.asarray(jax.nn.log_softmax(jnp.concatenate(rows, 0).astype(jnp.float32), -1))
    return logp, np.asarray(jnp.concatenate(picks, 1)).transpose(1, 0, 2) if routed else None


def _case(name, dtype, seed):
    c = CONFIGS[name]
    params = serve.make_params(c, seed, jax.devices()[0], dtype)
    if "router_bias" in params["layers"]:  # the program fills zeros: give selection something to shift
        params["layers"]["router_bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(seed), params["layers"]["router_bias"].shape)
    toks = np.random.default_rng(seed).integers(1, c.vocab_size, S).tolist()
    return c, dataclasses.asdict(c), params, toks


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_is_the_programs_mathematics_in_float32(name):
    c, model, params, toks = _case(name, jnp.float32, 3)
    got, picks = _program(c, params, toks)
    seq = np.asarray(toks, np.int32)
    want = ref.logprobs_at(model, params, seq, list(range(S)))
    assert np.abs(got - want).max() < 1e-4
    if picks is not None:
        # a float32 router moves a score by ~1e-7: the program's picks are the
        # reference's own, as sets (the order within the k is each side's)
        own = ref.own_picks(model, params, seq)
        assert picks.shape == own.shape == (S, c.n_layers - c.n_dense_layers, c.n_experts_active)
        assert np.array_equal(np.sort(picks, -1), np.sort(own, -1))
        logp, need = ref.follow_at(model, params, seq, list(range(S)), picks)
        assert float(need.max()) < 1e-5 and np.abs(got - logp).max() < 1e-4


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_followed_with_its_own_picks_is_unfollowed_bit_for_bit(name):
    _, model, params, toks = _case(name, jnp.float32, 5)
    seq, at = np.asarray(toks, np.int32), list(range(7, S))
    own = ref.own_picks(model, params, seq)
    logp, need = ref.follow_at(model, params, seq, at, own)
    assert np.array_equal(logp, ref.logprobs_at(model, params, seq, at))
    assert need.shape == own.shape[:2] and not need.any()


@pytest.mark.parametrize("seed", range(1, 11))
def test_on_the_served_bf16_tree_every_pick_is_admissible_and_the_logprobs_hold(seed):
    c, model, params, toks = _case("tiny-mla-moe", jnp.bfloat16, seed)
    got, picks = _program(c, params, toks)
    want, need = ref.follow_at(model, params, np.asarray(toks, np.int32), list(range(S)), picks)
    err = np.abs(got - want).max(-1)  # every position: nothing is left out
    assert float(need.max()) <= MARGIN, need.max()
    assert err.max() < TOL and err.mean() < TOL / 3, (err.max(), err.mean())


def test_unfollowed_the_same_tree_misses_by_more_than_any_tolerance():
    """Seeds 3 and 6 each hold a token the bf16 program routes otherwise than
    float32 arithmetic (read 1.44 and 2.0 unfollowed, 0.093 and 0.079
    followed): what the followed mode is for."""
    for seed in (3, 6):
        c, model, params, toks = _case("tiny-mla-moe", jnp.bfloat16, seed)
        got, picks = _program(c, params, toks)
        seq = np.asarray(toks, np.int32)
        assert np.abs(got - ref.logprobs_at(model, params, seq, list(range(S)))).max() > 1.0
        _, need = ref.follow_at(model, params, seq, list(range(S)), picks)
        assert 0 < float(need.max()) <= MARGIN and int((need > 0).sum()) == 1


def test_a_dense_bf16_tree_of_this_family_has_nothing_to_follow():
    c, model, params, toks = _case("tiny-mla-q", jnp.bfloat16, 2)
    got, _ = _program(c, params, toks)
    err = np.abs(got - ref.logprobs_at(model, params, np.asarray(toks, np.int32),
                                       list(range(S)))).max(-1)
    assert err.max() < TOL and err.mean() < TOL / 3
    assert not serve.follows(ref, model) and serve.follows(ref, dataclasses.asdict(
        CONFIGS["tiny-mla-moe"]))


@pytest.mark.parametrize("picks,need", [
    ([0, 1], 0.0),          # the top two
    ([1, 0], 0.0),          # in any order
    ([0, 2], 0.25),         # the third for the second: 0.75 - 0.5
    ([3, 0], 0.5),          # the weakest for the second
    ([2, 3], 0.75),         # both passed over: 1.0 against 0.25
    ([0, 0], np.inf),       # a repeated id
    ([0, 4], np.inf),       # no such expert
    ([0, 5], np.inf),       # an expert of a banned group
])
def test_need_is_how_far_the_scores_would_have_to_move(picks, need):
    choose = jnp.asarray([[1.0, 0.75, 0.5, 0.25, -jnp.inf, -jnp.inf]])
    if picks == [0, 4]:
        choose = choose[:, :4]
    assert float(ref.need_of(choose, jnp.asarray([picks]))[0]) == need


# -- serve.py's check on top of it ------------------------------------------


def _served_by_the_program(seed, dtype=jnp.float32, break_tree=None, n_prompt=12, n_out=10):
    """(model, params, sample, got) as `serve.served` would hand them over:
    one request, its greedy tokens, logprobs and picks from the program's
    forward over `params` (or over `break_tree(params)`: a fault planted in
    the served tree, which the reference does not see)."""
    c = CONFIGS["tiny-mla-moe"]
    model = dataclasses.asdict(c)
    params = serve.make_params(c, seed, jax.devices()[0], dtype)
    served_tree = break_tree(params) if break_tree else params
    fwd = jax.jit(lambda p, *a: llama.forward(c, p, *a, return_routed=True))
    pages = -(-(n_prompt + n_out) // PAGE)
    k, v = llama.make_kv_pool(c, pages + 1, PAGE, dtype=dtype)
    table = jnp.arange(pages, dtype=jnp.int32)[None, :]
    ids = np.random.default_rng(seed).integers(1, c.vocab_size, n_prompt).tolist()
    out = fwd(served_tree, jnp.asarray([ids]), jnp.arange(n_prompt)[None, :], k, v, table,
              jnp.asarray([n_prompt]))
    toks, lps, picks = [], [], [out[3][:, 0]]
    for t in range(n_prompt, n_prompt + n_out):
        row = jax.nn.log_softmax(out[0][0, -1].astype(jnp.float32))
        toks.append(int(row.argmax()))
        lps.append(float(row[toks[-1]]))
        if len(toks) < n_out:
            out = fwd(served_tree, jnp.asarray([[toks[-1]]]), jnp.asarray([[t]]), out[1], out[2],
                      table, jnp.asarray([t + 1]))
            picks.append(out[3][:, 0])
    picks = np.asarray(jnp.concatenate(picks, 1)).transpose(1, 0, 2)
    return model, params, [(ids, n_out)], [(toks, lps, picks)]


def test_the_check_of_a_routed_model_counts_every_token_and_every_pick():
    model, params, sample, got = _served_by_the_program(4)
    res = serve.check_against_reference(ref, model, params, sample, got, TOL, MARGIN)
    assert res["ok"] and res["tokens"] == 10
    assert res["picks"] == (12 + 10 - 1) * 2 and res["inadmissible"] == 0
    assert res["picks_differ"] == 0 and res["need_max"] == 0.0 and res["margin"] == MARGIN
    assert res["max_abs_logprob_err"] < 1e-4


def test_a_planted_inadmissible_pick_is_counted_and_fails():
    model, params, sample, got = _served_by_the_program(4)
    toks, lps, picks = got[0]
    own = ref.own_picks(model, params, np.asarray(sample[0][0] + toks[:-1], np.int32))
    # position 15, second expert layer: the reference's weakest expert in
    # place of its second pick
    worst = [e for e in range(4) if e not in own[15, 1]][-1]
    planted = picks.copy()
    planted[15, 1, 1] = worst
    res = serve.check_against_reference(ref, model, params, sample, [(toks, lps, planted)],
                                        TOL, MARGIN)
    assert res["inadmissible"] == 1 and res["picks_differ"] == 1 and res["need_max"] > MARGIN
    assert not res["ok"]
    # and a repeated id reads as a need no margin admits
    planted[15, 1] = planted[15, 1, 0]
    res = serve.check_against_reference(ref, model, params, sample, [(toks, lps, planted)],
                                        TOL, 1e6)
    assert res["inadmissible"] == 1 and res["need_max"] == serve.UNBOUNDED_NEED and not res["ok"]


def test_a_wrong_expert_matrix_passes_admissibility_and_fails_on_the_logprobs():
    def break_tree(params):
        # the LAST expert layer's down projections, halved: the routers see
        # what they saw, so every pick stays the router's
        layers = dict(params["layers"])
        layers["we_down"] = layers["we_down"].at[-1].multiply(0.5)
        return dict(params, layers=layers)

    model, params, sample, got = _served_by_the_program(4, break_tree=break_tree)
    res = serve.check_against_reference(ref, model, params, sample, got, TOL, MARGIN)
    assert res["inadmissible"] == 0 and res["picks_differ"] == 0
    assert res["max_abs_logprob_err"] > TOL and not res["ok"]


class _Engine:
    """An engine whose stream is a script: items as the program sends them."""

    def __init__(self, items):
        self.items, self.payloads = items, []

    async def generate(self, payload, ctx):
        self.payloads.append(payload)
        for item in self.items:
            yield item


def _items(starts):
    """A 3-token prompt and 3 served tokens: positions 0-4, one expert layer,
    k 2; `starts` are the `start` of the prefill item and of the two decode
    items that carry picks."""
    ids = lambda n: [[[0, 1]]] * n  # noqa: E731
    return [
        {"token_ids": [5], "routed_experts": {"start": starts[0], "ids": ids(3)}},
        {"token_ids": [6], "routed_experts": {"start": starts[1], "ids": ids(1)}},
        {"token_ids": [7], "routed_experts": {"start": starts[2], "ids": ids(1)},
         "finish_reason": "length"},
    ]


@pytest.mark.parametrize("starts,covered", [
    ((0, 3, 4), True),
    ((0, 4, 5), False),   # position 3 missing
    ((0, 3, 3), False),   # position 3 twice (a preempted request sends again)
    ((1, 4, 5), False),   # position 0 missing (a prefix-cache hit)
])
def test_a_stream_that_does_not_cover_every_position_once_is_short(starts, covered):
    engine = _Engine(_items(starts))
    sample = [([1, 2, 3], 3)]
    got = asyncio.run(serve.served(engine, sample, logprobs=False, picks=True))
    toks, lps, picks = got[0]
    assert toks == [5, 6, 7] and lps == []
    assert engine.payloads[0]["sampling"] == {"temperature": 0.0, "routed_experts": True}
    if covered:
        assert picks.shape == (5, 1, 2) and picks.dtype == np.int32
    else:
        assert picks is None
    stub = _Stub(np.zeros((5, 1), np.float32))
    res = serve.check_against_reference(stub, {}, None, sample, got, 0.1, 0.01)
    assert res["ok"] is covered and res["tokens"] == (3 if covered else 0)


def test_a_dense_models_payload_is_what_it_was():
    engine = _Engine([{"token_ids": [5, 6, 7], "finish_reason": "length"}])
    got = asyncio.run(serve.served(engine, [([1, 2, 3], 3)], logprobs=True))
    assert engine.payloads == [{"token_ids": [1, 2, 3],
                                "sampling": {"temperature": 0.0, "logprobs": 0},
                                "stop": {"max_tokens": 3, "stop_ids": [], "ignore_eos": True}}]
    assert json.dumps(engine.payloads[0]) == (
        '{"token_ids": [1, 2, 3], "sampling": {"temperature": 0.0, "logprobs": 0}, '
        '"stop": {"max_tokens": 3, "stop_ids": [], "ignore_eos": true}}')
    assert got == [([5, 6, 7], [], None)]


class _Stub:
    """A reference whose every logprob is right and whose needs are given."""

    V = 8

    def __init__(self, need):
        self.need = np.asarray(need, np.float32)

    def logprobs_at(self, model, params, tokens, at):
        return np.full((len(at), self.V), -np.log(self.V), np.float32)

    def follow_at(self, model, params, tokens, at, picks):
        assert picks.shape[0] == len(tokens)
        return self.logprobs_at(model, params, tokens, at), self.need


@pytest.mark.parametrize("over,ok", [(0, True), (1, False), (7, False)])
def test_no_inadmissible_pick_is_forgiven(over, ok):
    sample = [([1, 2, 3], 10)]
    picks = np.zeros((12, 2, 2), np.int32)
    got = [([j % _Stub.V for j in range(10)], [float(-np.log(_Stub.V))] * 10, picks)]
    need = np.full((12, 2), 0.001, np.float32)
    need.reshape(-1)[:over] = 0.5
    need[11, 1] = 0.0
    res = serve.check_against_reference(_Stub(need), {}, None, sample, got, 0.1, 0.01)
    assert res["ok"] is ok and res["tokens"] == 10  # every token compared, whatever its picks
    assert res["picks"] == 24 and res["picks_differ"] == 23 and res["inadmissible"] == over
    assert res["need_max"] == pytest.approx(0.5 if over else 0.001)
    # the same samples through a reference that does not follow: no routed keys
    plain = serve.check_against_reference(_Stub(need), {}, None, sample, got, 0.1)
    assert plain["ok"] and "picks" not in plain and "inadmissible" not in plain


def test_the_margin_has_no_default_and_no_meaning_without_a_followed_check():
    routed, dense = {"n_experts": 4}, {"n_experts": 0}
    assert serve.routing_margin(ref, routed, 0.01, "x") == 0.01
    assert serve.routing_margin(ref, dense, None, "x") is None
    assert serve.routing_margin(dense_ref, routed, None, "x") is None
    with pytest.raises(ValueError, match="has to state `correct_routing_margin`"):
        serve.routing_margin(ref, routed, None, "configs/x.json")
    with pytest.raises(ValueError, match="means nothing"):
        serve.routing_margin(dense_ref, routed, 0.01, "configs/x.json")
    with pytest.raises(ValueError, match="means nothing"):
        serve.routing_margin(ref, dense, 0.01, "configs/x.json")
