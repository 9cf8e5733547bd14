"""benchmark/reference/phi4flash_decoder.py against independent float64 numpy
loops, a piece at a time (the differential attention of paired heads, full
and under the window; the Mamba-1 recurrence without inner norms; the gated
memory unit and the cross attention through `_layer`), and whole against the
program's forward on the tiny preset (tests/test_sambay.py holds the program
to it on every path a sequence takes)."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, sambay
from dynamo_tpu.models.config import get_config
from dynamo_tpu.models.toolkit import make_kv_pool

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "_phi4flash_ref", os.path.join(BENCH, "reference", "phi4flash_decoder.py"))
ref = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ref)
RNG = np.random.default_rng(0)


def _r(*shape, scale=1.0):
    return (RNG.normal(size=shape) * scale).astype(np.float32)


def _softmax_rows(s):
    p = np.exp(s - s.max())
    return p / p.sum()


def _diff_loop(q, k, v, ap, layer, window, eps):
    """The published form, a (token, query pair) at a time, float64."""
    S, H, hd = q.shape
    Hk = k.shape[1]
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * layer)
    lam = ap["lam"].astype(np.float64)
    lam_full = np.exp(lam[:, 0] @ lam[:, 1]) - np.exp(lam[:, 2] @ lam[:, 3]) + lam0
    out = np.zeros((S, H // 2, 2 * hd))
    for t in range(S):
        lo = max(0, t - window + 1) if window else 0
        for i in range(H // 2):
            j = i // (H // Hk)
            vals = np.concatenate([v[lo:t + 1, 2 * j], v[lo:t + 1, 2 * j + 1]], axis=-1).astype(np.float64)
            a = [_softmax_rows(k[lo:t + 1, 2 * j + c].astype(np.float64) @ q[t, 2 * i + c] / np.sqrt(hd)) @ vals
                 for c in range(2)]
            x = a[0] - lam_full * a[1]
            out[t, i] = x / np.sqrt(np.mean(x * x) + eps) * ap["subln"] * (1 - lam0)
    return out.reshape(S, H * hd) @ ap["wo"].astype(np.float64) + ap["bo"]


@pytest.mark.parametrize("window", [0, 5])
def test_differential_attention_is_the_paired_heads_loop(window):
    S, H, Hk, hd, layer = 13, 8, 4, 8, 3
    q, k, v = _r(S, H, hd), _r(S, Hk, hd), _r(S, Hk, hd)
    ap = {"lam": _r(hd, 4, scale=0.3), "subln": _r(2 * hd), "wo": _r(H * hd, 12, scale=0.2), "bo": _r(12)}
    with jax.default_matmul_precision("highest"):
        got = ref._diff_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                  {n: jnp.asarray(a) for n, a in ap.items()}, layer, window, 1e-5)
    np.testing.assert_allclose(np.asarray(got), _diff_loop(q, k, v, ap, layer, window, 1e-5),
                               atol=2e-4, rtol=1e-4)


def test_the_mixer_is_the_recurrence_written_out():
    S, E, d, N, R, K = 9, 6, 8, 3, 2, 4
    mp = {"w_in": _r(E, 2 * d, scale=0.4), "w_conv": _r(K, d, scale=0.5), "b_conv": _r(d, scale=0.1),
          "w_x": _r(d, R + 2 * N, scale=0.4), "w_dt": _r(R, d, scale=0.5), "b_dt": _r(d, scale=0.1),
          "A_log": _r(N, d, scale=0.3), "D": _r(d), "w_out": _r(d, E, scale=0.4)}
    x = _r(S, E)
    with jax.default_matmul_precision("highest"):
        out, m = ref._mamba(jnp.asarray(x), {n: jnp.asarray(a) for n, a in mp.items()})
    f = {n: a.astype(np.float64) for n, a in mp.items()}
    az = x.astype(np.float64) @ f["w_in"]
    a, z = az[:, :d], az[:, d:]
    state, want, want_m = np.zeros((N, d)), np.zeros((S, E)), np.zeros((S, d))
    silu = lambda u: u / (1 + np.exp(-u))
    for t in range(S):
        conv = f["b_conv"].copy()
        for j in range(K):
            if t - (K - 1) + j >= 0:
                conv += f["w_conv"][j] * a[t - (K - 1) + j]
        c = silu(conv)
        dbc = c @ f["w_x"]
        dt = np.log1p(np.exp(dbc[:R] @ f["w_dt"] + f["b_dt"]))
        B, Cm = dbc[R:R + N], dbc[R + N:]
        state = np.exp(dt[None] * -np.exp(f["A_log"])) * state + (dt * c)[None] * B[:, None]
        want_m[t] = Cm @ state + f["D"] * c
        want[t] = (want_m[t] * silu(z[t])) @ f["w_out"]
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m), want_m, atol=2e-5, rtol=1e-4)


def test_a_memory_unit_and_a_cross_layer_read_what_the_self_decoder_left():
    """Through `_layer`: a GMU gates layer n/2's m of the same token, a cross
    layer attends with its own queries to the keys and values it is handed
    and leaves them as they were."""
    S, E, d, F, H, Hk, hd = 7, 16, 8, 12, 4, 2, 4
    lp = {"attn_norm_w": _r(E), "attn_norm_b": _r(E), "mlp_norm_w": _r(E), "mlp_norm_b": _r(E),
          "w_fc1": _r(E, 2 * F, scale=0.3), "w_fc2": _r(F, E, scale=0.3)}
    h, m = _r(S, E), _r(S, d)
    k, v = _r(S, Hk, hd), _r(S, Hk, hd)
    ln = lambda x, w, b: (x - x.mean(-1, keepdims=True)) / np.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b
    silu = lambda u: u / (1 + np.exp(-u))

    def mlp(x):
        gu = ln(x, lp["mlp_norm_w"], lp["mlp_norm_b"]).astype(np.float64) @ lp["w_fc1"]
        return x + (gu[:, F:] * silu(gu[:, :F])) @ lp["w_fc2"]

    carried = tuple(jnp.asarray(a) for a in (m, k, v))
    gmu = {"w_in": _r(E, d, scale=0.4), "w_out": _r(d, E, scale=0.4)}
    cross = {"wq": _r(E, H * hd, scale=0.4), "bq": _r(H * hd), "lam": _r(hd, 4, scale=0.3),
             "subln": _r(2 * hd), "wo": _r(H * hd, E, scale=0.3), "bo": _r(E)}
    with jax.default_matmul_precision("highest"):
        got, after = ref._layer(jnp.asarray(h), carried, lp, gmu, "gmu", 6, H, Hk, 0, 1e-5)
        x = ln(h, lp["attn_norm_w"], lp["attn_norm_b"]).astype(np.float64)
        want = mlp(h + (m * silu(x @ gmu["w_in"])) @ gmu["w_out"])
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-4)
        got, after = ref._layer(jnp.asarray(h), carried, lp, cross, "cross", 7, H, Hk, 0, 1e-5)
    q = (x @ cross["wq"] + cross["bq"]).reshape(S, H, hd)
    want = mlp(h + _diff_loop(q, k, v, cross, 7, 0, 1e-5))
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-4, rtol=1e-4)
    for a, b in zip(after, carried):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_whole_reference_is_the_programs_forward_on_the_tiny_preset():
    c = get_config("tiny-phi4flash")
    params = llama.init_params(c, jax.random.PRNGKey(5), jnp.float32)
    toks = RNG.integers(1, c.vocab_size, size=37)
    kp, vp = make_kv_pool(c, 8, 8, jnp.float32)
    st = sambay.SIDE.make_pool(c, (2, 8), 8, jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4, 5, 0]], jnp.int32)
    lg, *_ = jax.jit(lambda *a, **k: sambay.forward(c, *a, **k))(
        params, jnp.asarray(toks[None]), jnp.arange(37)[None], kp, vp, table,
        jnp.asarray([37]), state=st, slots=(jnp.asarray([1]), table))
    want = ref.logprobs_at(dataclasses.asdict(c), params, toks, list(range(37)))
    assert np.abs(np.asarray(jax.nn.log_softmax(lg[0], axis=-1)) - want).max() < 2e-4
    assert ref.layer_kinds(32) == list(get_config("phi-4-mini-flash-reasoning").layer_kinds)
