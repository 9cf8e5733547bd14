"""reference/mistral4_decoder.py against the program's forward (prefill, then
decode one token at a time through the paged latent pool) on seeded random
weights at toy sizes, logits and not tokens: latent attention with a compressed
query and the position-dependent query scale (a toy `orig`, so it is not 1),
a router over all 16 experts and an expert layer that holds four of them.

In float32 the two agree to rounding (limit 1e-4). With every expert held and
the query scale off, the new file is the untouched `mla_moe_decoder.py` to
rounding, which ties the family's two references together. Followed with its
own picks it returns `logprobs_at`'s rows bit for bit. In bfloat16, as served,
the followed reference holds every position to the rehearsal's tolerance and
every served pick to the rehearsal's margin (the configuration file's
`rehearse` group); a wrong held expert matrix passes admissibility and fails
on the logprobs. The margin and tolerance at published widths are chip
readings: PERF.md section 6, PR 33."""

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
ref = _load(os.path.join("reference", "mistral4_decoder.py"), "bench_reference_mistral4")
whole_ref = _load(os.path.join("reference", "mla_moe_decoder.py"), "bench_reference_mla_moe")
with open(os.path.join(BENCH, "configs", "mistral-small-4-119b.json")) as _f:
    _CFG = json.load(_f)
MARGIN = _CFG["rehearse"]["correct_routing_margin"]
TOL = _CFG["rehearse"]["correct_tolerance"]

TOY = PRESETS["tiny-mistral4"]
CONFIGS = {
    "second-quarter": TOY,
    "first-quarter": TOY.with_(expert_first=0),
    "last-half-no-query-rank": TOY.with_(n_experts_held=8, expert_first=8, q_lora_rank=0),
    "every-expert": TOY.with_(n_experts_held=0, expert_first=0),
    "sigmoid-unscaled": TOY.with_(moe_scoring="sigmoid", attn_qscale_beta=0.0),
}


def _program(c, params, toks):
    """(log-softmax rows [S, V], the router's picks [S, L, k]): a prefill of
    N_PREFILL tokens, then decode, as the step programs run it."""
    fwd = jax.jit(lambda *a: llama.forward(c, params, *a, return_routed=True))
    pages = -(-S // PAGE)
    k, v = llama.make_kv_pool(c, pages + 1, PAGE, dtype=params["embed"].dtype)
    table = jnp.arange(pages, dtype=jnp.int32)[None, :]
    out = fwd(jnp.asarray([toks[:N_PREFILL]]), jnp.arange(N_PREFILL)[None, :], k, v,
              table, jnp.asarray([N_PREFILL]))
    rows, picks = [out[0][0]], [out[3][:, 0]]
    for t in range(N_PREFILL, S):
        out = fwd(jnp.asarray([[toks[t]]]), jnp.asarray([[t]]), out[1], out[2], table,
                  jnp.asarray([t + 1]))
        rows.append(out[0][0])
        picks.append(out[3][:, 0])
    logp = np.asarray(jax.nn.log_softmax(jnp.concatenate(rows, 0).astype(jnp.float32), -1))
    return logp, np.asarray(jnp.concatenate(picks, 1)).transpose(1, 0, 2)


def _case(name, dtype, seed):
    c = CONFIGS[name]
    params = serve.make_params(c, seed, jax.devices()[0], dtype)
    toks = np.random.default_rng(seed).integers(1, c.vocab_size, S).tolist()
    return c, dataclasses.asdict(c), params, toks


def test_make_params_draws_the_held_experts_and_the_whole_router():
    _, _, params, _ = _case("second-quarter", jnp.bfloat16, 1)
    lay = params["layers"]
    assert lay["we_gate"].shape == (2, 4, 64, 64) and lay["w_router"].shape == (2, 64, 16)
    assert "layers_dense" not in params and float(jnp.abs(lay["we_down"]).max()) > 0


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_is_the_programs_mathematics_in_float32(name):
    c, model, params, toks = _case(name, jnp.float32, 3)
    got, picks = _program(c, params, toks)
    seq = np.asarray(toks, np.int32)
    want = ref.logprobs_at(model, params, seq, list(range(S)))
    assert np.abs(got - want).max() < 1e-4
    own = ref.own_picks(model, params, seq)
    assert picks.shape == own.shape == (S, c.n_layers, c.n_experts_active)
    assert np.array_equal(np.sort(picks, -1), np.sort(own, -1))
    held = set(range(c.expert_first, c.expert_first + c.experts_held))
    assert not c.holds_share or set(own.ravel().tolist()) - held  # ids over the full width
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


def test_with_every_expert_held_and_no_query_scale_it_is_the_uncut_family_reference():
    c = TOY.with_(n_experts_held=0, expert_first=0, attn_qscale_beta=0.0)
    model = dataclasses.asdict(c)
    params = serve.make_params(c, 9, jax.devices()[0], jnp.float32)
    seq = np.random.default_rng(9).integers(1, c.vocab_size, S).astype(np.int32)
    at = list(range(S))
    np.testing.assert_allclose(ref.logprobs_at(model, params, seq, at),
                               whole_ref.logprobs_at(model, params, seq, at), atol=2e-5)
    assert np.array_equal(ref.own_picks(model, params, seq), whole_ref.own_picks(model, params, seq))


def test_the_query_scale_and_the_share_each_move_the_logits():
    _, model, params, toks = _case("second-quarter", jnp.float32, 4)
    seq, at = np.asarray(toks, np.int32), list(range(S))
    base = ref.logprobs_at(model, params, seq, at)
    unscaled = ref.logprobs_at({**model, "attn_qscale_beta": 0.0}, params, seq, at)
    err = np.abs(base - unscaled).max(-1)
    assert err[:8].max() == 0.0 < err[8:].max()  # exactly 1 below `orig` (8 here)
    other = ref.logprobs_at({**model, "expert_first": 8}, params, seq, at)  # same matrices, other ids
    assert np.abs(base - other).max() > 1e-2


@pytest.mark.parametrize("seed", range(1, 7))
def test_on_the_served_bf16_tree_every_pick_is_admissible_and_the_logprobs_hold(seed):
    c, model, params, toks = _case("second-quarter", jnp.bfloat16, seed)
    got, picks = _program(c, params, toks)
    want, need = ref.follow_at(model, params, np.asarray(toks, np.int32), list(range(S)), picks)
    assert float(need.max()) <= MARGIN
    err = np.abs(got - want).max(-1)
    assert err.max() <= TOL and err.mean() <= TOL / 3


def test_a_wrong_held_expert_matrix_passes_admissibility_and_fails_on_the_logprobs():
    c, model, params, toks = _case("second-quarter", jnp.bfloat16, 2)
    got, picks = _program(c, params, toks)
    broken = dict(params, layers=dict(params["layers"]))
    broken["layers"]["we_down"] = -params["layers"]["we_down"]
    bad, bad_picks = _program(c, broken, toks)
    seq = np.asarray(toks, np.int32)
    want, need = ref.follow_at(model, params, seq, list(range(S)), bad_picks)
    assert np.isfinite(need).all()  # the picks are a router's: admissible by construction or nearly
    assert np.abs(bad - want).max() > 4 * np.abs(
        got - ref.follow_at(model, params, seq, list(range(S)), picks)[0]).max()


def test_serve_follows_this_reference_and_wants_its_margin():
    model = dict(_CFG["model"])
    assert serve.follows(ref, model)
    assert serve.routing_margin(ref, model, _CFG["correct_routing_margin"], "cfg") == \
        float(_CFG["correct_routing_margin"])
    with pytest.raises(ValueError, match="correct_routing_margin"):
        serve.routing_margin(ref, model, None, "cfg")
