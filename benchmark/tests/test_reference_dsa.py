"""reference/dsa_moe_decoder.py on seeded random weights at toy sizes (the
program's `tiny-dsa`: latent attention with a compressed query, the lightning
indexer at index_topk 8, a group-limited sigmoid router of which the second
quarter is held, a leading dense layer). Followed with its own picks it is the
unfollowed one bit for bit and needs nothing; with every position selected it
is the untouched `mla_moe_decoder.py` (every expert held) to rounding, which
ties it to the family's references; the program's forward through the paged
pools agrees with it to rounding in float32. tests/test_dsa.py holds it to
transformers' deepseek_v3 and the program to it path by path."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join("reference", "dsa_moe_decoder.py"), "bench_reference_dsa")
whole_ref = _load(os.path.join("reference", "mla_moe_decoder.py"), "bench_reference_mla_moe")
TOY = PRESETS["tiny-dsa"]
TOKS = np.random.default_rng(2).integers(1, TOY.vocab_size, 40)
AT = list(range(40))


def _params(c, dtype=jnp.float32):
    return llama.init_params(c, jax.random.PRNGKey(9), dtype)


def test_it_imports_nothing_from_the_program_and_sets_highest_precision():
    with open(os.path.join(BENCH, "reference", "dsa_moe_decoder.py")) as f:
        src = f.read()
    assert "dynamo_tpu" not in src.split('"""', 2)[2]
    assert src.count('jax.default_matmul_precision("highest")') == 3


def test_followed_with_its_own_picks_it_is_the_unfollowed_one_bit_for_bit():
    model, p = dataclasses.asdict(TOY), _params(TOY)
    own = ref.own_picks(model, p, TOKS)
    assert own.shape == (40, 2, TOY.n_experts_active) and own.max() >= TOY.expert_first + 4
    plain = ref.logprobs_at(model, p, TOKS, AT)
    followed, need = ref.follow_at(model, p, TOKS, AT, own)
    assert followed.tobytes() == plain.tobytes() and float(need.max()) == 0.0
    # other picks are another model: swap every position's first pick
    other = own.copy()
    other[..., 0] = (other[..., 0] + 1) % TOY.n_experts
    moved, need = ref.follow_at(model, p, TOKS, AT, other)
    assert need.max() > 0 and np.abs(moved - plain).max() > 1e-3


def test_with_every_position_selected_it_is_the_uncut_familys_reference():
    c = TOY.with_(n_experts_held=0, expert_first=0, index_topk=64)
    model, p = dataclasses.asdict(c), _params(c)
    got = ref.logprobs_at(model, p, TOKS, AT)
    want = whole_ref.logprobs_at(model, p, TOKS, AT)
    assert np.abs(got - want).max() < 1e-4
    selecting = ref.logprobs_at(dataclasses.asdict(c.with_(index_topk=8)), p, TOKS, AT)
    err = np.abs(selecting - want).max(-1)
    assert err[:8].max() < 1e-5 and err[12:].min() > 1e-3


def test_the_program_agrees_with_it_through_the_paged_pools():
    model, p = dataclasses.asdict(TOY), _params(TOY)
    pt = jnp.arange(1, 11, dtype=jnp.int32)[None, :]
    kp, vp = llama.make_kv_pool(TOY, 12, 4, jnp.float32)
    fwd = jax.jit(llama.forward, static_argnums=0)
    rows, a = [], 0
    with jax.default_matmul_precision("highest"):
        for b in [21] + list(range(22, 41)):
            out, kp, vp = fwd(TOY, p, jnp.asarray(TOKS[None, a:b]), jnp.arange(a, b)[None],
                              kp, vp, pt, jnp.asarray([b]))
            rows.append(np.asarray(out[0]))
            a = b
    got = np.asarray(jax.nn.log_softmax(np.concatenate(rows), axis=-1))
    assert np.abs(got - ref.logprobs_at(model, p, TOKS, AT)).max() < 2e-4
