"""benchmark/reference/ling3_decoder.py by hand: its KDA layer against an
independent numpy loop in float64 (the equations written out token by token,
channel by channel, from ISSUE 49's text), its convolution against a loop,
and `follow_at` with its own picks returning `logprobs_at` bit for bit."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _module(os.path.join(BENCH, "reference", "ling3_decoder.py"), "ling3_reference_by_hand")
C = PRESETS["tiny-ling"]
MODEL = {k: getattr(C, k) for k in (
    "n_layers", "n_heads", "norm_eps", "kv_lora_rank", "qk_rope_head_dim",
    "qk_nope_head_dim", "v_head_dim", "rope_theta", "kda_layer_period", "kda_head_dim",
    "kda_conv", "kda_gate_lower", "n_experts", "n_experts_active", "n_experts_held",
    "expert_first", "moe_ffn_dim", "n_shared_experts", "moe_scoring", "moe_norm_topk",
    "moe_routed_scale", "n_dense_layers", "n_expert_groups", "topk_groups", "max_seq_len")}


def _params(seed=0):
    p = llama.init_params(C, jax.random.PRNGKey(seed), jnp.float32)
    rng = np.random.default_rng(seed)
    for n in ("A_log", "dt_bias", "o_norm"):
        p["kda"][n] = p["kda"][n] + jnp.asarray(rng.normal(size=p["kda"][n].shape) * 0.3, jnp.float32)
    return p


def _sig(x):
    return 1.0 / (1.0 + np.exp(-x))


def kda_by_hand(h, norm, kp):
    """One KDA layer in float64 numpy, every sum a loop's."""
    H, dk, eps, K = C.n_heads, C.kda_head_dim, C.norm_eps, C.kda_conv
    f = lambda a: np.asarray(a, np.float64)
    S_len = h.shape[0]
    x = h / np.sqrt((h * h).mean(-1, keepdims=True) + eps) * f(norm)
    a = np.concatenate([x @ f(kp["wq"]), x @ f(kp["wk"]), x @ f(kp["wv"])], axis=-1)
    w = f(kp["conv"])
    conv = np.zeros_like(a)
    for t in range(S_len):
        for j in range(K):  # tap j reads the input K - 1 - j tokens back
            src = t - (K - 1) + j
            if src >= 0:
                conv[t] += w[j] * a[src]
    qkv = conv * _sig(conv)
    q, k, v = (qkv[:, i * H * dk:(i + 1) * H * dk].reshape(S_len, H, dk) for i in range(3))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    g = C.kda_gate_lower * _sig(np.repeat(np.exp(f(kp["A_log"])), dk)
                                * (x @ f(kp["wa"]) + f(kp["dt_bias"])))
    g = g.reshape(S_len, H, dk)
    assert (g > C.kda_gate_lower).all() and (g < 0).all()
    beta = _sig(x @ f(kp["w_beta"]))
    gate = _sig(x @ f(kp["w_g"]))
    out = np.zeros((S_len, H, dk))
    for hd in range(H):
        S = np.zeros((dk, dk))
        for t in range(S_len):
            S = np.exp(g[t, hd])[:, None] * S
            S = S + beta[t, hd] * np.outer(k[t, hd], v[t, hd] - k[t, hd] @ S)
            o = S.T @ q[t, hd]
            o = o / np.sqrt((o * o).mean() + eps) * f(kp["o_norm"])
            out[t, hd] = o * gate[t, hd]
    return h + out.reshape(S_len, H * dk) @ f(kp["wo"])


def test_the_kda_layer_is_the_equations_by_hand():
    p = _params()
    kp = jax.tree.map(lambda a: a[1], p["kda"])
    norm = p["layers"]["attn_norm"][0]
    h = np.random.default_rng(1).normal(size=(23, C.dim))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref._kda(jnp.asarray(h, jnp.float32), norm, kp, MODEL))
    assert np.abs(got - kda_by_hand(h, norm, kp)).max() < 2e-5


def test_the_layer_rule_is_the_configs():
    assert [ref.is_attn_layer({"kda_layer_period": 6}, l) for l in range(12)] == [
        False] * 5 + [True] + [False] * 5 + [True]


def test_follow_at_with_its_own_picks_is_logprobs_at_bit_for_bit():
    p = _params(2)
    toks = np.random.default_rng(3).integers(1, C.vocab_size, size=26)
    at = [0, 7, 24, 25]
    own = ref.logprobs_at(MODEL, p, toks, at)
    picks = ref.own_picks(MODEL, p, toks)
    assert picks.shape == (26, C.n_layers - C.n_dense_layers, C.n_experts_active)
    logp, need = ref.follow_at(MODEL, p, toks, at, picks)
    np.testing.assert_array_equal(logp, own)
    assert need.shape == (26, 5) and not need.any()
    # another expert in one position's set: its need is what the scores say,
    # and only that position's later rows move
    other = picks.copy()
    other[10, 2, 0] = next(e for e in range(C.n_experts) if e not in picks[10, 2])
    logp2, need2 = ref.follow_at(MODEL, p, toks, at, other)
    assert need2[10, 2] > 0 and not np.delete(need2.reshape(-1), 10 * 5 + 2).any()
    np.testing.assert_array_equal(logp2[:2], own[:2])
