"""ops/kda.py: the chunkwise form of the delta rule with a decay a channel
and the one-token update, each as plain jnp and as a Pallas kernel in
interpret mode, against the token-by-token recurrence in float32 (and that
against a float64 numpy loop). Small sizes, seeded, on the CPU: the
tolerances are float32 rounding (1e-5 on outputs of size ~0.5: two float32
programs that order their sums differently, exponents up to e^40 apart)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import kda

H, D = 2, 16
TOL = 1e-5
LOWER = -5.0
FORMS = {"jnp": kda.kda_chunk_jnp,
         "pallas": functools.partial(kda.kda_chunk, interpret=True)}


def draw(T, seed=0, g=None, H=H, d=D):
    """(S0, q, k, v, g, beta) as models/ling.py hands them over: q and k
    L2-normed a head, g in (-5, 0), beta in (0, 1), a carried-in state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (T, H, d)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    if g is None:
        g = LOWER * jax.nn.sigmoid(jax.random.normal(ks[3], (T, H, d)) * 2 - 3)
    else:
        g = jnp.full((T, H, d), g, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return jax.random.normal(ks[5], (H, d, d)), q, k, v, g, beta


def loop64(S0, q, k, v, g, beta):
    """The equations in float64 numpy, written apart from ops/kda.py."""
    S = np.asarray(S0, np.float64)
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    out = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[..., None] * S
        u = v[t] - np.einsum("hk,hkv->hv", k[t], S)
        S = S + beta[t][:, None, None] * k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hk,hkv->hv", q[t], S))
    return np.stack(out), S


def test_the_recurrence_is_the_equations():
    a = draw(70, seed=1)
    o, S = kda.kda_recurrence(*a)
    o64, S64 = loop64(*a)
    assert np.abs(np.asarray(o) - o64).max() < 1e-6
    assert np.abs(np.asarray(S) - S64).max() < 1e-5


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("T", [64, 200, 17, 128])
def test_chunk_form_is_the_recurrence(form, T):
    """One block, several blocks with a ragged tail (padded inside), fewer
    tokens than a sub-block, whole blocks: from a carried-in state."""
    a = draw(T, seed=T)
    o, S = kda.kda_recurrence(*a)
    o2, S2 = FORMS[form](*a)
    assert o2.shape == o.shape
    assert np.abs(np.asarray(o2) - np.asarray(o)).max() < TOL
    assert np.abs(np.asarray(S2) - np.asarray(S)).max() < TOL


@pytest.mark.parametrize("form", list(FORMS))
def test_chunk_form_at_the_decay_bound_over_two_blocks(form):
    """Every channel at the lower bound for 130 tokens: e^(5 n) passes
    float32 after 17 tokens, so anything that took e^(-G) over a whole block
    would be inf or nan here; held to the float64 loop."""
    a = draw(130, seed=5, g=LOWER * (1 - 1e-6))
    o64, S64 = loop64(*a)
    o, S = FORMS[form](*a)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    assert np.abs(np.asarray(o) - o64).max() < TOL
    assert np.abs(np.asarray(S) - S64).max() < TOL


@pytest.mark.parametrize("form", list(FORMS))
def test_channels_at_the_bound_beside_channels_that_keep(form):
    """Half the channels forget within a token, half keep for hundreds: the
    two ends of what one block has to hold at once."""
    S0, q, k, v, g, beta = draw(150, seed=6)
    g = jnp.where(jnp.arange(D) % 2 == 0, LOWER * (1 - 1e-6), -0.002) + 0 * g
    o64, S64 = loop64(S0, q, k, v, g, beta)
    o, S = FORMS[form](S0, q, k, v, g, beta)
    assert np.abs(np.asarray(o) - o64).max() < TOL
    assert np.abs(np.asarray(S) - S64).max() < TOL


@pytest.mark.parametrize("form", list(FORMS))
def test_keys_that_share_a_direction_over_three_blocks(form):
    """What a SiLU in front of the L2 norm gives at real widths: every key
    near one direction, slow decays, betas near 1, so every entry of
    B tril(A, -1) is a few tenths. The series of its powers cancels to noise
    there (the first chip run's NaN, PERF.md section 6, PR 49); forward
    substitution stays at float32 rounding."""
    S0, q, k, v, g, beta = draw(200, seed=3)
    base = jax.random.normal(jax.random.PRNGKey(99), (1, H, D))
    k = base + 0.5 * jax.random.normal(jax.random.PRNGKey(98), k.shape)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = (S0, q, k, v, 0.02 * g, 0.5 + 0.5 * beta)
    o64, S64 = loop64(*a)
    o, S = FORMS[form](*a)
    assert np.abs(np.asarray(o) - o64).max() < TOL
    assert np.abs(np.asarray(S) - S64).max() < TOL


@pytest.mark.parametrize("form", list(FORMS))
def test_padding_tokens_are_the_identity(form):
    """beta 0 and g 0 behind the real tokens (models/ling.py sets them):
    the state after 90 real tokens, whatever the padding's q, k, v."""
    S0, q, k, v, g, beta = draw(128, seed=7)
    live = jnp.arange(128) < 90
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    o, S = FORMS[form](S0, q, k, v, g, beta)
    o_w, S_w = kda.kda_recurrence(S0, q[:90], k[:90], v[:90], g[:90], beta[:90])
    assert np.abs(np.asarray(o[:90]) - np.asarray(o_w)).max() < TOL
    assert np.abs(np.asarray(S) - np.asarray(S_w)).max() < TOL


@pytest.mark.parametrize("form", list(FORMS))
def test_chunks_carry_the_state_across_their_ends(form):
    """200 tokens as 64 + 100 + 36, each chunk from the state the last one
    left: the whole run's outputs and final state."""
    a = draw(200, seed=8)
    o_w, S_w = kda.kda_recurrence(*a)
    S, outs, start = a[0], [], 0
    for n in (64, 100, 36):
        o, S = FORMS[form](S, *(x[start:start + n] for x in a[1:]))
        outs.append(o)
        start += n
    assert np.abs(np.asarray(jnp.concatenate(outs)) - np.asarray(o_w)).max() < TOL
    assert np.abs(np.asarray(S) - np.asarray(S_w)).max() < TOL


def _rows(B=5, seed=9, n_h=4):
    _, q, k, v, g, beta = draw(B, seed=seed, H=n_h)
    pool = jax.random.normal(jax.random.PRNGKey(seed + 1), (3, 8, n_h, D, D))
    slots = jnp.asarray([3, 5, 1, 0, 0])
    live = jnp.asarray([True, True, True, False, False])
    fresh = jnp.asarray([False, True, False, False, False])
    return pool, slots, live, fresh, q, k, v, g, beta


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_update_is_one_step_of_the_recurrence(form):
    pool, slots, live, fresh, q, k, v, g, beta = _rows()
    op = (functools.partial(kda.kda_update, interpret=True) if form == "pallas"
          else kda.kda_update_jnp)
    o, new = op(pool, 1, slots, live, fresh, q, k, v, g, beta)
    for r in range(3):
        S0 = jnp.zeros_like(pool[1, 0]) if bool(fresh[r]) else pool[1, slots[r]]
        o_w, S_w = kda.kda_recurrence(S0, *(a[r:r + 1] for a in (q, k, v, g, beta)))
        assert np.abs(np.asarray(o[r]) - np.asarray(o_w[0])).max() < 1e-6
        assert np.abs(np.asarray(new[1, slots[r]]) - np.asarray(S_w)).max() < 1e-6
    assert not np.asarray(o[3:]).any()  # a padding row gives nothing


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_padding_rows_and_other_layers_keep_every_slot_bit_for_bit(form):
    pool, slots, live, fresh, q, k, v, g, beta = _rows()
    op = (functools.partial(kda.kda_update, interpret=True) if form == "pallas"
          else kda.kda_update_jnp)
    _, new = op(pool, 1, slots, live, fresh, q, k, v, g, beta)
    untouched = [s for s in range(8) if s not in (3, 5, 1)]
    np.testing.assert_array_equal(np.asarray(new[1, untouched]), np.asarray(pool[1, untouched]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(pool[2]))


def test_update_kernel_agrees_with_its_jnp_form_over_head_blocks():
    """32 heads: two blocks of HEAD_BLOCK heads a row."""
    pool, slots, live, fresh, q, k, v, g, beta = _rows(n_h=32)
    assert kda.head_block(32) == kda.HEAD_BLOCK == 16
    o1, p1 = kda.kda_update_jnp(pool, 2, slots, live, fresh, q, k, v, g, beta)
    o2, p2 = kda.kda_update(pool, 2, slots, live, fresh, q, k, v, g, beta, interpret=True)
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() < 1e-6
    assert np.abs(np.asarray(p1) - np.asarray(p2)).max() < 1e-6
