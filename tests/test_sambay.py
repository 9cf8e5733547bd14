"""Phi-4-mini-flash's decoder-hybrid-decoder (models/sambay.py): the program
against the plain reference on every path a sequence takes (one prefill,
chunks down to one token and across the window's edge, decode through the
state slot, the window pages and the shared layer, rows of unequal length,
the ragged mixed step, preemption, a slot and window pages taken over), the
cross-decoder on the sampled rows alone against the every-row forward, and
the pieces against independent arithmetic (transformers' MambaMixer, a
float64 numpy loop). Seeded random weights, the tiny preset, float32 on the
CPU: 2e-4 on a logprob where two float32 programs order their sums
differently, exact where one program is run two ways.
"""

import asyncio
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import worker
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import SeqState
from dynamo_tpu.models import jamba, llama, sambay
from dynamo_tpu.models.config import get_config
from dynamo_tpu.models.toolkit import make_kv_pool, paged_attention_jnp
from dynamo_tpu.ops import ssm
from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata
from dynamo_tpu.runtime.context import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # float32 programs that order their sums differently
PS, NP, MP = 8, 24, 8  # window 16: two pages


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/phi4flash_decoder.py", "_phi4flash_reference")
C = get_config("tiny-phi4flash")
MODEL = dataclasses.asdict(C)


def _params(seed=0, c=C):
    """The tree with every fill made random too (the biases, A, D, the norm
    weights), as a checkpoint has them."""
    params = llama.init_params(c, jax.random.PRNGKey(seed), jnp.float32)
    rng = np.random.default_rng(seed + 1)
    drawn = {"embed", "w_fc1", "w_fc2", "w_in", "w_conv", "w_x", "w_dt",
             "w_out", "wqkv", "wq", "wo", "lam"}

    def rnd(path, a):
        if getattr(path[-1], "key", None) in drawn:
            return a
        return a + jnp.asarray(rng.normal(size=a.shape) * 0.2, a.dtype)

    return jax.tree_util.tree_map_with_path(rnd, params)


@pytest.fixture(scope="module")
def params():
    return _params()


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, C.vocab_size, size=n)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _pools(slots=5, wpages=NP, poison=7.0):
    """The KV pool and both side pools with junk in every unit: a
    sequence's first token must not read what its units held."""
    kp, vp = make_kv_pool(C, NP, PS, jnp.float32)
    state = sambay.SIDE.make_pool(C, (slots, wpages), PS, jnp.float32)
    return kp + poison, vp + poison, jax.tree.map(lambda a: a + poison, state)


# one compiled program a shape (an eager forward compiles its scans anew
# at every call)
FORWARD = jax.jit(functools.partial(sambay.forward, C),
                  static_argnames=("attn_impl",))


def _row(table):
    return list(table) + [0] * (MP - len(table))


def _chunk(params, pools, toks, start, n, table, slot, wtable, S=48,
           every_row=False, **kw):
    """One prefill chunk of `n` tokens from `start` at bucket S
    (`every_row`: the oracle that knows nothing of `last_index`, the
    cross-decoder on every token and the row gathered here)."""
    t = np.zeros((1, S), np.int32)
    t[0, :n] = toks[start:start + n]
    p = np.full((1, S), -1, np.int32)
    p[0, :n] = np.arange(start, start + n)
    # (a Python `sampled` is static: the eager forward elides at trace time)
    fwd = (functools.partial(sambay.forward, C)
           if isinstance(kw.get("sampled"), bool) else FORWARD)
    lg, kp, vp, st = fwd(
        params, jnp.asarray(t), jnp.asarray(p), pools[0], pools[1],
        jnp.asarray([_row(table)], jnp.int32), jnp.asarray([start + n]),
        None if every_row else jnp.int32(n - 1), state=pools[2],
        slots=(jnp.asarray([slot]), jnp.asarray([_row(wtable)], jnp.int32)), **kw)
    return lg[0, n - 1 if every_row else 0], (kp, vp, st)


def _units(pools, pages, slot, wpages):
    """What one sequence holds of the three pools."""
    kp, vp, st = pools
    return [np.asarray(a) for a in (
        kp[:, pages], vp[:, pages], st["state"]["S"][:, slot],
        st["state"]["conv"][:, slot], st["window"]["k"][:, wpages],
        st["window"]["v"][:, wpages])]


# -- the layers' kinds -------------------------------------------------------


def test_the_kinds_of_layer_follow_from_the_configs_keys():
    assert C.layer_kinds == ("mamba", "window", "mamba", "window", "mamba",
                             "full", "gmu", "cross")
    full = get_config("phi-4-mini-flash-reasoning")
    kinds = full.layer_kinds
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full" and kinds[15] == "window"
    assert list(kinds) == ref.layer_kinds(32)
    assert (full.kv_layers, full.mamba_layers, full.is_hybrid) == (1, 9, False)
    # the pools hold a pair of KV heads as one head twice as wide
    assert make_kv_pool(C, 4, PS)[0].shape == (1, 4, PS, 1, 32)
    assert sambay.make_window_pool(C, 4, PS)["k"].shape == (2, 4, PS, 1, 32)
    with pytest.raises(ValueError, match="n_layers a multiple of 4"):
        C.with_(n_layers=6)
    with pytest.raises(ValueError, match="Phi-4-mini-flash's"):
        C.with_(tie_embeddings=False)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(jax.eval_shape(
        lambda: llama.init_params(full, jax.random.PRNGKey(0)))))
    assert 3.84e9 < n < 3.86e9


# -- the program against the reference ---------------------------------------


def test_full_forward_agrees_with_the_reference(params):
    toks = _tokens(45, 3)  # past the window of 16, over six pages
    want = ref.logprobs_at(MODEL, params, toks, list(range(45)))
    kp, vp, st = _pools()
    lg, *_ = FORWARD(
        params, jnp.asarray(toks[None]), jnp.arange(45)[None], kp, vp,
        jnp.asarray([_row([1, 2, 3, 4, 5, 6])], jnp.int32), jnp.asarray([45]),
        state=st, slots=(jnp.asarray([3]),
                         jnp.asarray([_row([7, 8, 9, 10, 11, 12])], jnp.int32)))
    assert np.abs(_logp(lg[0]) - want).max() < TOL


def test_ten_pairs_take_sixteen_pool_heads_and_change_nothing():
    """Phi-4-mini-flash's head count at toy widths: 10 KV pairs in pools of
    16 heads (whole 8-row tiles), zeros behind the pairs; a chunk, then a
    decode step, are the reference's."""
    c = C.with_(dim=320, n_heads=20, n_kv_heads=20)
    assert c.pool_heads == 16 and C.pool_heads == 1
    assert get_config("phi-4-mini-flash-reasoning").pool_heads == 16
    params = _params(3, c)
    toks = _tokens(27, 9)
    want = ref.logprobs_at(dataclasses.asdict(c), params, toks, [25, 26])
    kp, vp = make_kv_pool(c, 8, PS, jnp.float32)
    assert kp.shape == (1, 8, PS, 16, 32)
    st = sambay.SIDE.make_pool(c, (3, 8), PS, jnp.float32)
    assert sambay.SIDE.unit_bytes(c, PS, jnp.float32)[1] == 2 * PS * 16 * 32 * 2 * 4
    fwd = jax.jit(functools.partial(sambay.forward, c))
    table, wtab = jnp.asarray([_row([1, 2, 3, 4])], jnp.int32), jnp.asarray([_row([5, 6, 7, 3])], jnp.int32)
    lg, kp, vp, st = fwd(params, jnp.asarray(toks[None, :26]), jnp.arange(26)[None], kp, vp,
                         table, jnp.asarray([26]), jnp.int32(25), state=st,
                         slots=(jnp.asarray([2]), wtab))
    assert np.abs(_logp(lg[0, 0]) - want[0]).max() < TOL
    assert not np.asarray(kp[..., 10:, :]).any() and np.asarray(kp[0, 1, :, :10]).all()
    lg, *_ = fwd(params, jnp.asarray(toks[None, 26:]), jnp.asarray([[26]]), kp, vp, table,
                 jnp.asarray([27]), state=st, slots=(jnp.asarray([2]), wtab))
    assert np.abs(_logp(lg[0, 0]) - want[1]).max() < TOL


@pytest.mark.parametrize("sizes", [[40], [13, 27], [15, 1, 1, 23], [16, 8, 16]])
def test_a_prompt_in_any_chunks_gives_one_state(params, sizes):
    """Chunks down to one token and across the window's edge (16): the same
    last logits, and the same slot, window pages and KV pages. Only the last
    chunk is sampled; the others run no cross-decoder."""
    toks = _tokens(40, 4)
    table, wtable = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
    want = ref.logprobs_at(MODEL, params, toks, [39])[0]
    pools, start = _pools(), 0
    for i, n in enumerate(sizes):
        lg, pools = _chunk(params, pools, toks, start, n, table, 3, wtable,
                           sampled=jnp.asarray(i == len(sizes) - 1))
        start += n
    assert np.abs(_logp(lg) - want).max() < TOL
    _, whole = _chunk(params, _pools(), toks, 0, 40, table, 3, wtable)
    for a, b in zip(_units(pools, table, 3, wtable), _units(whole, table, 3, wtable)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def _interpreted_kernels(monkeypatch):
    from dynamo_tpu.ops import paged_attention as pa_ops
    from dynamo_tpu.ops import ragged_paged_attention as rg_ops

    for mod, name in ((pa_ops, "decode_paged_attention"), (rg_ops, "ragged_paged_attention"),
                      (ssm, "ssm_update"), (ssm, "ssm_scan")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name), interpret=True))


@pytest.mark.parametrize("attn_impl", ["jnp", "pallas"])
def test_decode_through_slot_window_pages_and_the_shared_layer(params, attn_impl, monkeypatch):
    """Two sequences of unequal length decode side by side before a padding
    row (live rows lead: ops/ssm.py): each step's logprobs are the reference's full pass for
    its own sequence, x crossing a page and the window's edge on the way,
    and the padding row changes no unit."""
    _interpreted_kernels(monkeypatch)
    x, y = _tokens(36, 3), _tokens(12, 5)
    wx, wy = ref.logprobs_at(MODEL, params, x, list(range(36))), \
        ref.logprobs_at(MODEL, params, y, list(range(12)))
    _, pools = _chunk(params, _pools(), x, 0, 30, [1, 2, 3, 4, 5], 3, [6, 7, 8, 9, 10])
    _, (kp, vp, st) = _chunk(params, pools, y, 0, 7, [11, 12], 1, [13, 14])
    table = jnp.asarray([_row([1, 2, 3, 4, 5]), _row([11, 12]), [0] * MP], jnp.int32)
    # (x's first window page lies wholly below what it still sees: freed)
    wtab = jnp.asarray([_row([0, 7, 8, 9, 10]), _row([13, 14]), [0] * MP], jnp.int32)
    for i in range(5):
        px, py = 30 + i, 7 + i
        before = st
        lg, kp, vp, st = FORWARD(
            params, jnp.asarray([[x[px]], [y[py]], [0]], jnp.int32),
            jnp.asarray([[px], [py], [-1]], jnp.int32), kp, vp, table,
            jnp.asarray([px + 1, py + 1, 0]), state=st,
            slots=(jnp.asarray([3, 1, 0]), wtab), attn_impl=attn_impl)
        assert np.abs(_logp(lg[0, 0]) - wx[px]).max() < TOL
        assert np.abs(_logp(lg[1, 0]) - wy[py]).max() < TOL
        for a, b in zip(jax.tree.leaves(st["state"]), jax.tree.leaves(before["state"])):
            np.testing.assert_array_equal(np.asarray(a[:, [0, 2, 4]]), np.asarray(b[:, [0, 2, 4]]))
            for slot in (1, 3):
                assert not np.array_equal(np.asarray(a[:, slot]), np.asarray(b[:, slot]))


@pytest.mark.parametrize("attn_impl", ["jnp", "pallas"])
def test_ragged_step_with_a_decode_row_and_two_chunks(params, attn_impl, monkeypatch):
    """One flat step: X decodes its 31st token, Y's first ten tokens start
    slot 1 (junk in it), Z goes on from its ninth token past the window's
    edge. Each row's logprobs are the reference's for its own sequence; the
    cross-decoder ran on the three gathered rows alone."""
    _interpreted_kernels(monkeypatch)
    x, y, z = _tokens(31, 3), _tokens(10, 5), _tokens(25, 6)
    _, pools = _chunk(params, _pools(), x, 0, 30, [1, 2, 3, 4], 3, [5, 6, 7, 8])
    _, (kp, vp, st) = _chunk(params, pools, z, 0, 9, [9, 10, 11, 12], 2, [13, 14, 15, 16])
    q_lens, T = [1, 10, 16], 32
    tables = [[1, 2, 3, 4], [17, 18], [9, 10, 11, 12]]
    wtables = [[0, 6, 7, 8], [19, 20], [13, 14, 15, 16]]
    md = build_ragged_metadata(q_lens, [30, 0, 9], [31, 10, 25], tables, T,
                               q_block=8, max_pages=MP)
    flat = np.zeros(T, np.int32)
    flat[0], flat[1:11], flat[11:27] = x[30], y, z[9:25]
    cap = md["seg_page_table"].shape[0]
    gather = np.zeros(cap, np.int32)
    gather[:3] = md["last_index"]
    sides = [(3, wtables[0]), (1, wtables[1]), (2, wtables[2])]
    lg, _, _, st2 = FORWARD(
        params, jnp.asarray(flat[None]), jnp.asarray(md["tok_positions"])[None],
        kp, vp, jnp.asarray(md["tok_page_table"]), jnp.asarray(md["tok_kv_lens"]),
        last_index=jnp.asarray(gather),
        ragged=tuple(jnp.asarray(md[k]) for k in ("seg_page_table", "seg_kv_lens", "meta")),
        state=st, slots=sambay.SIDE.segs(sides, q_lens, cap, T, MP), attn_impl=attn_impl)
    assert lg.shape == (1, cap, C.vocab_size)
    got = _logp(lg[0])
    for row, (toks, at) in enumerate(((x, 30), (y, 9), (z, 24))):
        want = ref.logprobs_at(MODEL, params, toks, [at])[0]
        assert np.abs(got[row] - want).max() < TOL, row
    for a, b in zip(jax.tree.leaves(st2["state"]), jax.tree.leaves(st["state"])):
        np.testing.assert_array_equal(np.asarray(a[:, [0, 4]]), np.asarray(b[:, [0, 4]]))


# -- the cross-decoder on the sampled rows alone ------------------------------


def test_sampled_rows_only_is_the_every_row_forward_where_it_is_sampled(params):
    """The forward that runs layers 6 and 7 on the row at last_index alone
    gives that row what the forward that runs them on every token gives it,
    and leaves every cache as that one does; a chunk nobody samples
    (`sampled` False) leaves them so too and runs no cross-decoder: the
    compiled program's FLOPs fall by the cross-decoder's and the head's."""
    toks = _tokens(40, 8)
    table, wtable = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
    every_lg, every = _chunk(params, _pools(), toks, 0, 40, table, 3, wtable,
                             every_row=True)
    rows_lg, rows = _chunk(params, _pools(), toks, 0, 40, table, 3, wtable)
    none_lg, none = _chunk(params, _pools(), toks, 0, 40, table, 3, wtable,
                           sampled=jnp.asarray(False))
    static_lg, static = _chunk(params, _pools(), toks, 0, 40, table, 3, wtable,
                               sampled=False)
    assert np.abs(np.asarray(rows_lg) - np.asarray(every_lg)).max() < 1e-4
    assert not np.asarray(none_lg).any() and not np.asarray(static_lg).any()
    # (another compiled program fuses its sums otherwise; the traced flag
    # is one program run two ways)
    for a, b in zip(jax.tree.leaves(none), jax.tree.leaves(rows)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for other in (rows, static):
        for a, b in zip(jax.tree.leaves(other), jax.tree.leaves(every)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def flops(every_row=False, **kw):
        kp, vp, st = _pools()
        S = 48
        fn = functools.partial(sambay.forward, C, **kw)
        lowered = jax.jit(fn).lower(
            params, jnp.zeros((1, S), jnp.int32), jnp.arange(S)[None], kp, vp,
            jnp.asarray([_row(table + [11])], jnp.int32), jnp.asarray([S]),
            None if every_row else jnp.int32(S - 1), state=st,
            slots=(jnp.asarray([3]), jnp.asarray([_row(wtable + [12])], jnp.int32)))
        return lowered.compile().cost_analysis()["flops"]

    every_row, sampled_row, unsampled = (
        flops(every_row=True), flops(), flops(sampled=False))
    # two of the eight layers and the head are the cross-decoder's
    d, E, F, V = C.mamba_d_inner, C.dim, C.ffn_dim, C.vocab_size
    cross = 2 * (2 * E * d + 2 * E * E + 2 * 3 * E * F) + 2 * E * V  # a token
    assert every_row - sampled_row > 0.9 * 47 * cross
    assert sampled_row - unsampled > 0.9 * cross
    assert unsampled < 0.8 * every_row


# -- the pieces against independent arithmetic --------------------------------


def test_zero_padded_queries_on_a_paired_head_are_the_paired_heads():
    """`[q0 | 0]` and `[0 | q1]` on a pool head `[k0 | k1]` at the scale of
    one head: the two softmaxes of differential attention, written out."""
    rng = np.random.default_rng(0)
    Hp, Gp, hd, n = 2, 2, 16, 11
    q = rng.normal(size=(1, 1, Hp, Gp, 2, hd)).astype(np.float32)
    k = rng.normal(size=(2, PS, Hp, 2, hd)).astype(np.float32)
    v = rng.normal(size=(2, PS, Hp, 2 * hd)).astype(np.float32)
    got = paged_attention_jnp(
        sambay.pad_queries(jnp.asarray(q)), jnp.asarray(k.reshape(2, PS, Hp, 2 * hd)),
        jnp.asarray(v), jnp.asarray([[0, 1]]), jnp.asarray([[n - 1]]),
        jnp.asarray([n]), scale=hd ** -0.5)
    got = np.asarray(got).reshape(Hp, Gp, 2, 2 * hd)
    kf, vf = k.reshape(2 * PS, Hp, 2, hd)[:n], v.reshape(2 * PS, Hp, 2 * hd)[:n]
    for j in range(Hp):
        for g in range(Gp):
            for c in range(2):
                s = kf[:, j, c] @ q[0, 0, j, g, c] / np.sqrt(hd)
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ vf[:, j]
                np.testing.assert_allclose(got[j, g, c], want, atol=1e-5)


def test_the_differential_combination_against_a_float64_loop():
    rng = np.random.default_rng(1)
    Hp, Gp, hd, layer, eps = 2, 2, 16, 5, 1e-5
    a = rng.normal(size=(3, Hp, 2 * Gp, 2 * hd)).astype(np.float32)
    lam = (rng.normal(size=(hd, 4)) * 0.3).astype(np.float32)
    w = rng.normal(size=(2 * hd,)).astype(np.float32)
    got = np.asarray(sambay.diff_combine(jnp.asarray(a), jnp.asarray(lam),
                                         jnp.asarray(w), layer, eps))
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * layer)
    l64 = lam.astype(np.float64)
    full = np.exp(l64[:, 0] @ l64[:, 1]) - np.exp(l64[:, 2] @ l64[:, 3]) + lam0
    want = np.zeros((3, Hp * Gp, 2 * hd))
    for t in range(3):
        for j in range(Hp):
            for g in range(Gp):
                x = a[t, j, 2 * g].astype(np.float64) - full * a[t, j, 2 * g + 1]
                x = x / np.sqrt(np.mean(x * x) + eps)
                want[t, j * Gp + g] = x * w * (1 - lam0)
    np.testing.assert_allclose(got, want.reshape(3, -1), atol=2e-5)


def test_the_mixer_without_inner_norms_is_transformers_mamba_mixer():
    """The program's mixer (`inner_norms=False`) and the reference's `_mamba`
    against `transformers.models.mamba.MambaMixer.slow_forward`, float32,
    random biases too; the scan's output handed out is the reference's m."""
    torch = pytest.importorskip("torch")
    from transformers.models.mamba.modeling_mamba import MambaConfig, MambaMixer

    E, d, N, R, K, S = C.dim, C.mamba_d_inner, C.mamba_d_state, C.mamba_dt_rank, 4, 19
    torch.manual_seed(0)
    hf = MambaMixer(MambaConfig(
        hidden_size=E, state_size=N, conv_kernel=K, expand=C.mamba_expand,
        time_step_rank=R, use_conv_bias=True, use_bias=False,
        num_hidden_layers=1), layer_idx=0).eval()
    with torch.no_grad():
        for p in hf.parameters():
            p.copy_(torch.randn_like(p) * 0.3)
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    mp = {"w_in": sd["in_proj.weight"].T, "w_conv": sd["conv1d.weight"][:, 0].T,
          "b_conv": sd["conv1d.bias"], "w_x": sd["x_proj.weight"].T,
          "w_dt": sd["dt_proj.weight"].T, "b_dt": sd["dt_proj.bias"],
          "A_log": sd["A_log"].T, "D": sd["D"], "w_out": sd["out_proj.weight"].T}
    mp = {k: jnp.asarray(v) for k, v in mp.items()}
    x = np.random.default_rng(2).normal(size=(S, E)).astype(np.float32)
    with torch.no_grad():
        want = hf.slow_forward(torch.from_numpy(x)[None])[0].numpy()
    out, m = ref._mamba(jnp.asarray(x), mp)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-4, rtol=1e-4)
    plan = jamba._plan(jnp.arange(S)[None], jnp.asarray([1]), False)
    state = jamba.make_state_pool(C, 2, conv_dtype=jnp.float32)
    stacked = {k: v[None] for k, v in mp.items()}
    got, _, got_m = jamba._mamba_mixer(
        C, jax.tree.map(lambda a: a[0], stacked), jnp.asarray(x), plan,
        jax.tree.map(lambda a: a[:1], state), 0, "jnp", inner_norms=False,
        scan_out=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(m), atol=2e-4, rtol=1e-4)


# -- through the engine ------------------------------------------------------


def _engine(monkeypatch, params, **engine_kw):
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    args = worker.parse_args([
        "--model", "tiny-phi4flash", "--max-batch", "4", "--chunk-size", "16",
        "--mixed-prefill-tokens", "12", "--mixed-prefill-seqs", "2",
        "--mixed-min-chunk", "4"])
    runner = ModelRunner(
        C, num_pages=96, page_size=4, max_pages_per_seq=32, decode_buckets=(2, 4),
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


def _samp(n):
    return {"temperature": [0.0] * n, "top_k": [0] * n, "top_p": [1.0] * n,
            "seeds": [0] * n, "rep": [1.0] * n, "freq": [0.0] * n,
            "presence": [0.0] * n}


def _held_to_reference(params, ids, toks, lps):
    seq = np.asarray(list(ids) + toks[:-1], np.int32)
    at = list(range(len(ids) - 1, len(seq)))
    want = ref.logprobs_at(MODEL, params, seq, at)
    assert np.abs(want[np.arange(len(toks)), toks] - np.asarray(lps)).max() < TOL
    assert float((want.max(-1) - want[np.arange(len(toks)), toks]).max()) < TOL


async def test_engine_sizes_both_pools_and_serves_through_every_program(monkeypatch, params):
    engine, runner = _engine(monkeypatch, params)
    try:
        sched = engine.scheduler
        assert runner.side_kind == sched.side.kind == "state+window"
        assert runner.ragged_mixed and runner.fuses_mixed and runner.skips_unsampled
        assert runner.side_units == sched.side.units
        assert runner.side_units[0] == 4 + 1  # a slot a row, and scratch
        assert runner.side_unit_bytes == (
            jamba.state_slot_bytes(C, conv_dtype=jnp.float32),
            sambay.window_page_bytes(C, 4, 4))
        assert runner.k_pool.shape[0] == 1  # the full layer alone
        assert runner.state["window"]["k"].shape[:2] == (2, runner.side_units[1])
        assert not sched.enable_prefix_cache
        report = runner.device_report()
        assert report["state_slots"] == 5 and report["window_pages"] == runner.side_units[1]
        assert report["state_pool_bytes"] == 5 * runner.side_unit_bytes[0]
        assert report["window_pool_bytes"] == runner.side_units[1] * runner.side_unit_bytes[1]
        # junk in every unit: nothing a sequence reads before it wrote it
        runner.state = jax.tree.map(lambda a: a + 9.0, runner.state)
        lead = _tokens(12, 10)
        rest = [_tokens(n, 11 + n) for n in (19, 26, 40)]

        async def late(ids, **kw):
            await asyncio.sleep(0.05)
            return await _serve(engine, ids, 5, **kw)

        got = await asyncio.gather(_serve(engine, lead, 30), *(late(r) for r in rest))
        for ids, (toks, lps) in zip([lead] + rest, got):
            _held_to_reference(params, ids, toks, lps)
        recs = engine.recorder.snapshot()
        assert max(r.state_slots_used for r in recs) >= 2
        assert all(r.state_slots_total == 4 for r in recs)
        assert max(r.window_pages_used for r in recs) >= 4
        assert sched.side.parts[1].freed > 0  # 42 tokens under a window of 16
        assert sum(r.ssm_scan_tokens for r in recs) >= sum(len(r) for r in rest)
        # chunks of 16 at most: a prompt of 40 runs two that nobody samples
        chunk_tokens = sum(r.chunk_tokens for r in recs)
        assert sum(r.yoco_skipped_tokens for r in recs) == chunk_tokens - 4
        assert sum(r.yoco_cross_rows for r in recs) == 4 + sum(
            r.decode_seqs * r.decode_steps for r in recs)
        assert sched.side.parts[0].used == 0
        window = sched.side.parts[1].pool
        assert window.n_free == window.num_pages - 1
        # without logprobs the same drive rides the ragged program
        calls0 = runner.compile_stats()["ragged"]["calls"]
        outs = await asyncio.gather(
            _serve(engine, lead, 30, logprobs=False),
            *(late(r, logprobs=False) for r in rest))
        assert runner.compile_stats()["ragged"]["calls"] > calls0
        assert [o[0] for o in outs] == [g[0] for g in got]
        ragged = [r for r in engine.recorder.snapshot() if r.ragged]
        assert any(r.ssm_scan_segments > r.n_chunks for r in ragged)
        # a ragged step gathers one row a segment, whatever its chunk ends
        # (the runner counts a dispatch where it is enqueued: a decode
        # dispatch run ahead may land on its neighbour's record)
        assert all(r.yoco_skipped_tokens == r.chunk_tokens - r.n_chunks for r in ragged)
        assert sum(r.yoco_cross_rows for r in ragged) >= sum(
            r.decode_seqs * r.decode_steps + r.n_chunks for r in ragged)
        # the runner counts what it hands its programs: a chunk told
        # `sampled=False` ran no cross-decoder row, one that ends its prompt
        # one, a decode dispatch a row a step
        import types

        rec = types.SimpleNamespace()
        runner.fill_record(rec)
        runner.prefill([1] * 8, 0, [1], 0, sampled=False)
        runner.fill_record(rec)
        assert (rec.yoco_cross_rows, rec.yoco_skipped_tokens) == (0, 8)
        runner.prefill([1] * 8, 8, [1, 2], 8)
        runner.decode_multi(2, [1] * 3, [0] * 3, [[0]] * 3, _samp(3), 1)
        runner.fill_record(rec)
        assert (rec.yoco_cross_rows, rec.yoco_skipped_tokens) == (1 + 2 * 3, 7)
    finally:
        engine.stop()


async def test_preempted_and_cancelled_sequences_leave_nothing_behind(monkeypatch, params):
    """A sequence preempted mid-decode gives its slot and its window pages
    back and, readmitted, computes again from position 0; one cancelled
    mid-decode frees both, and the next sequence takes that very slot and
    those pages. Both end with the logprobs of a fresh run."""
    engine, runner = _engine(monkeypatch, params)
    try:
        sched = engine.scheduler
        slots, window = sched.side.parts
        a, b = _tokens(22, 20), _tokens(11, 21)
        plan, seen = sched.step_plan, {}

        def preempting():
            run = [s for s in sched.active if s.state == SeqState.RUNNING]
            if run and run[0].n_generated >= 4 and not seen:
                held = tuple(run[0].side)
                sched._preempt(run[0])
                seen["held"] = held
                assert run[0].side is None
            return plan()

        sched.step_plan = preempting
        toks, lps = await _serve(engine, a, 12)
        assert seen["held"][0] > 0 and len(toks) == 12
        _held_to_reference(params, a, toks, lps)
        sched.step_plan = plan
        await _serve(engine, a, 30, cancel_after=6)
        for _ in range(200):
            if not sched.active:
                break
            await asyncio.sleep(0.01)
        assert slots.used == 0 and window.pool.n_free == window.pool.num_pages - 1
        freed = slots._free[-1]
        assert float(jnp.abs(runner.state["state"]["S"][:, freed]).max()) > 0  # a's, stale

        async def watch():
            while not sched.active:
                await asyncio.sleep(0.001)
            return sched.active[0].side[0]

        slot, (toks, lps) = await asyncio.gather(watch(), _serve(engine, b, 8))
        assert slot == freed
        _held_to_reference(params, b, toks, lps)
    finally:
        engine.stop()


# -- what it refuses, in both kinds' words -------------------------------------


def test_every_path_that_moves_kv_by_pages_alone_refuses_in_both_kinds_words(
        monkeypatch, params):
    words = ("state-space layers", "a window pool")
    with pytest.raises(ValueError, match="tier demotion.*" + ".*".join(words)):
        _engine(monkeypatch, params, host_kv_blocks=8)
    with pytest.raises(ValueError, match="speculative decoding.*" + ".*".join(words)):
        _engine(monkeypatch, params, spec_ngram=True)
    kw = dict(num_pages=8, page_size=4, params=params, dtype=jnp.float32)
    with pytest.raises(NotImplementedError,
                       match="quantized KV cache.*" + ".*".join(words)):
        ModelRunner(C, kv_quantize="int8", **kw)
    with pytest.raises(NotImplementedError, match="not sharded"):
        from dynamo_tpu.parallel.mesh import MeshConfig

        ModelRunner(C, MeshConfig(model=2), **kw)
    with pytest.raises(NotImplementedError, match="draft model.*" + words[0]):
        ModelRunner(C, draft_config=get_config("tiny"), **kw)
    engine, runner = _engine(monkeypatch, params)
    try:
        assert all(w in engine.side.no_prefix for w in words)
        for call, what in (
                (lambda: runner.export_pages_device([1]), "KV export"),
                (lambda: runner.import_pages_device([1], 0, None, None), "KV import"),
                (lambda: runner.export_pages([1]), "KV export"),
                (lambda: runner.import_pages([1], 0, {}), "KV import"),
                (lambda: runner.verify_spec([1], [0], [[1]], [[2]], {}, 1),
                 "speculative verify")):
            with pytest.raises(NotImplementedError, match=what + ".*" + ".*".join(words)):
                call()

        async def ask(**extra):
            items = []
            async for item in engine.generate(
                    {"token_ids": [1, 2, 3],
                     "sampling": {"temperature": 0.0, **extra.pop("sampling", {})},
                     "stop": {"max_tokens": 2}, **extra}, Context()):
                items.append(item)
            return items[-1]

        err = asyncio.run(ask(annotations={"disagg": "prefill"}))
        assert err["finish_reason"] == "error" and "disaggregated" in err["error"]
        assert all(w in err["error"] for w in words)
        err = asyncio.run(ask(sampling={"n": 2}))
        assert err["finish_reason"] == "error" and "n > 1" in err["error"]
        assert all(w in err["error"] for w in words)
    finally:
        engine.stop()
    with pytest.raises(NotImplementedError, match="models/sambay.forward"):
        llama.forward(C, params, jnp.zeros((1, 1), jnp.int32),
                      jnp.zeros((1, 1), jnp.int32), *[None] * 4)
    from dynamo_tpu.engine.weights import load_hf_checkpoint

    with pytest.raises(NotImplementedError, match="no checkpoint loader"):
        load_hf_checkpoint("/nonexistent", C)
