"""Jamba's hybrid decoder (models/jamba.py): the plain reference against
transformers, the program against the reference, and the state slot through
every path a sequence takes: chunked prefill, the decode loop, the ragged
mixed step, preemption, cancellation and reuse. Seeded random weights, small
sizes, float32 on the CPU (so the tolerances are float32 rounding: 2e-4 on a
logprob where two float32 programs order their sums differently, exact where
one program is run two ways).
"""

import asyncio
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import worker
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import Scheduler, SeqState
from dynamo_tpu.engine.side_cache import StateSlots
from dynamo_tpu.engine.kv_pool import PagePool
from dynamo_tpu.engine.weights import (
    config_from_hf,
    jamba_to_hf_state,
    load_hf_checkpoint,
)
from dynamo_tpu.models import jamba, llama
from dynamo_tpu.models.config import get_config
from dynamo_tpu.models.toolkit import make_kv_pool
from dynamo_tpu.ops import ssm
from dynamo_tpu.ops.ragged_paged_attention import build_ragged_metadata
from dynamo_tpu.runtime.context import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4  # float32 programs that order their sums differently


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/jamba_decoder.py", "_jamba_reference")
C = get_config("tiny-jamba")
MODEL = {k: getattr(C, k) for k in (
    "n_layers", "n_heads", "n_kv_heads", "norm_eps", "attn_layer_period",
    "attn_layer_offset")}


def _params(seed=0, dtype=jnp.float32):
    """tiny-jamba's tree with every fill made random too (the biases, A, D
    and the norm weights), as a checkpoint has them."""
    params = llama.init_params(C, jax.random.PRNGKey(seed), dtype)
    rng = np.random.default_rng(seed + 1)

    def rnd(a):
        return a + jnp.asarray(rng.normal(size=a.shape) * 0.3, a.dtype)

    for n in ("b_conv", "b_dt", "D", "dt_norm", "b_norm", "c_norm", "A_log"):
        params["mamba"][n] = rnd(params["mamba"][n])
    for n in ("attn_norm", "mlp_norm"):
        params["layers"][n] = rnd(params["layers"][n])
    params["norm_f"] = rnd(params["norm_f"])
    return params


@pytest.fixture(scope="module")
def params():
    return _params()


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, C.vocab_size, size=n)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# -- the reference against transformers -------------------------------------


def test_reference_agrees_with_transformers_jamba(params):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hc = transformers.JambaConfig(
        vocab_size=C.vocab_size, hidden_size=C.dim, intermediate_size=C.ffn_dim,
        num_hidden_layers=C.n_layers, num_attention_heads=C.n_heads,
        num_key_value_heads=C.n_kv_heads, rms_norm_eps=C.norm_eps,
        num_experts=1, num_experts_per_tok=1,
        attn_layer_period=C.attn_layer_period,
        attn_layer_offset=C.attn_layer_offset, mamba_d_state=C.mamba_d_state,
        mamba_d_conv=C.mamba_d_conv, mamba_expand=C.mamba_expand,
        mamba_dt_rank=C.mamba_dt_rank, mamba_conv_bias=True,
        mamba_proj_bias=False, use_mamba_kernels=False,  # its slow_forward
        tie_word_embeddings=True)
    m = transformers.JambaForCausalLM(hc).float().eval()
    sd = {k: torch.tensor(v) for k, v in jamba_to_hf_state(C, params).items()}
    missing, unexpected = m.load_state_dict(sd, strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    toks = _tokens(37, 3)
    with torch.no_grad():
        theirs = torch.log_softmax(
            m(torch.tensor(toks[None]), use_cache=False).logits[0], -1).numpy()
    ours = ref.logprobs_at(MODEL, params, toks, list(range(len(toks))))
    assert np.abs(ours - theirs).max() < TOL


# -- the loader -------------------------------------------------------------


def test_loader_takes_a_jamba_checkpoint_and_refuses_routed_ones(tmp_path, params):
    from safetensors.numpy import save_file

    cfg = {"model_type": "jamba", "vocab_size": C.vocab_size,
           "hidden_size": C.dim, "intermediate_size": C.ffn_dim,
           "num_hidden_layers": C.n_layers, "num_attention_heads": C.n_heads,
           "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "num_experts": 1,
           "attn_layer_period": 4, "attn_layer_offset": 2, "mamba_d_state": 4,
           "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
           "mamba_conv_bias": True, "mamba_proj_bias": False,
           "tie_word_embeddings": True, "max_position_embeddings": 2048}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    state = jamba_to_hf_state(C, params)
    state.pop("lm_head.weight")  # tied: a checkpoint keeps the embedding once
    save_file(state, str(tmp_path / "model.safetensors"))
    c = config_from_hf(str(tmp_path), name="tiny-jamba")
    assert c == C
    got = load_hf_checkpoint(str(tmp_path), c, dtype="float32")
    want = jax.tree.map(np.asarray, params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    (tmp_path / "config.json").write_text(json.dumps({**cfg, "num_experts": 16}))
    with pytest.raises(NotImplementedError, match="num_experts = 16"):
        config_from_hf(str(tmp_path))


def test_init_params_draws_no_leaf_of_rank_one():
    """benchmark/serve.py redraws a drawn leaf at shape[-2]^-0.5: what the
    program draws from its key must have an `in` axis, and the fills are the
    published initialisation."""
    c = get_config("tiny-jamba")
    a = llama.init_params(c, jax.random.PRNGKey(0), jnp.float32)
    b = llama.init_params(c, jax.random.PRNGKey(1), jnp.float32)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)):
        name = path[-1].key
        drawn = not np.array_equal(np.asarray(x), np.asarray(y))
        fill = name in ("A_log", "D", "b_dt", "b_conv") or name.endswith("norm") \
            or name == "norm_f"
        assert drawn != fill, name
        if drawn:
            assert x.ndim >= 2, name
    m = a["mamba"]
    np.testing.assert_allclose(np.exp(np.asarray(m["A_log"][0, :, 0])), [1, 2, 3, 4], rtol=1e-6)
    steps = np.asarray(jax.nn.softplus(m["b_dt"][0]))
    assert abs(steps[0] - 1e-3) < 1e-6 and abs(steps[-1] - 1e-1) < 1e-5
    assert float(jnp.abs(m["b_conv"]).max()) == 0 and float(m["D"].min()) == 1
    assert a["mamba"]["w_conv"].shape[-2:] == (c.mamba_d_conv, c.mamba_d_inner)


# -- the program's forward against the reference -----------------------------

PS, NP, MP = 16, 32, 8


def _pools(slots=6, poison=7.0):
    """KV pools and a state pool whose every slot holds junk: a sequence's
    first token must not read what its slot held."""
    kp, vp = make_kv_pool(C, NP, PS, jnp.float32)
    state = jamba.make_state_pool(C, slots, conv_dtype=jnp.float32)
    return kp, vp, jax.tree.map(lambda a: a + poison, state)


def _chunk(params, pools, toks, start, n, table, slot, S=32):
    """One prefill chunk of `n` tokens from `start` at bucket S."""
    t = np.zeros((1, S), np.int32)
    t[0, :n] = toks[start:start + n]
    p = np.full((1, S), -1, np.int32)
    p[0, :n] = np.arange(start, start + n)
    lg, kp, vp, st = jamba.forward(
        C, params, jnp.asarray(t), jnp.asarray(p), pools[0], pools[1],
        jnp.asarray([table + [0] * (MP - len(table))], jnp.int32),
        jnp.asarray([start + n]), jnp.int32(n - 1), state=pools[2],
        slots=jnp.asarray([slot]))
    return lg[0, 0], (kp, vp, st)


def test_full_forward_agrees_with_the_reference(params):
    toks = _tokens(37, 3)
    want = ref.logprobs_at(MODEL, params, toks, list(range(37)))
    kp, vp, st = _pools()
    lg, *_ = jamba.forward(
        C, params, jnp.asarray(toks[None]), jnp.arange(37)[None], kp, vp,
        jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0]], jnp.int32), jnp.asarray([37]),
        state=st, slots=jnp.asarray([3]))
    assert np.abs(_logp(lg[0]) - want).max() < TOL


@pytest.mark.parametrize("n_layers", [7, 10, 3])
def test_a_depth_that_is_no_multiple_of_the_period(n_layers):
    """Whole periods scan; what is left is written out: 7 layers end on an
    attention layer, 10 on two Mamba layers, 3 hold one attention layer and
    no whole period."""
    c = C.with_(n_layers=n_layers)
    params = llama.init_params(c, jax.random.PRNGKey(2), jnp.float32)
    toks = _tokens(21, 8)
    want = ref.logprobs_at({**MODEL, "n_layers": n_layers}, params, toks, list(range(21)))
    kp, vp = make_kv_pool(c, NP, PS, jnp.float32)
    assert kp.shape[0] == c.kv_layers == len(c.attn_layers)
    lg, *_ = jamba.forward(
        c, params, jnp.asarray(toks[None]), jnp.arange(21)[None], kp, vp,
        jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0]], jnp.int32), jnp.asarray([21]),
        state=jamba.make_state_pool(c, 2, conv_dtype=jnp.float32), slots=jnp.asarray([1]))
    assert np.abs(_logp(lg[0]) - want).max() < TOL


@pytest.mark.parametrize("sizes", [[30], [13, 17], [5, 6, 7, 8, 4]])
def test_a_prompt_in_any_chunks_gives_one_state(params, sizes):
    toks = _tokens(30, 4)
    whole_lg, whole = _chunk(params, _pools(), toks, 0, 30, [1, 2], 3)
    pools, start = _pools(), 0
    for n in sizes:
        lg, pools = _chunk(params, pools, toks, start, n, [1, 2], 3)
        start += n
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(whole_lg))
    for a, b in zip(jax.tree.leaves(pools[2]), jax.tree.leaves(whole[2])):
        np.testing.assert_array_equal(np.asarray(a[:, 3]), np.asarray(b[:, 3]))


def _interpreted_kernels(monkeypatch):
    """attn_impl="pallas" on the CPU: the four kernels of a hybrid step in
    interpret mode (the attention ones at ONE KV head, so the one-head
    routine over the 4-d view of the pool, ops/paged_attention.py)."""
    import functools

    from dynamo_tpu.ops import paged_attention as pa_ops
    from dynamo_tpu.ops import ragged_paged_attention as rg_ops

    for mod, name in ((pa_ops, "decode_paged_attention"), (rg_ops, "ragged_paged_attention"),
                      (ssm, "ssm_update"), (ssm, "ssm_scan")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name), interpret=True))


@pytest.mark.parametrize("attn_impl", ["jnp", "pallas"])
def test_prefill_then_decode_through_slot_and_pages(params, attn_impl, monkeypatch):
    """30 tokens prefilled, 7 decoded one at a time beside two padding rows:
    every logprob is the reference's full pass, and the padding rows (which
    name the scratch slot, and have no position) change no slot."""
    _interpreted_kernels(monkeypatch)
    toks = _tokens(37, 3)
    want = ref.logprobs_at(MODEL, params, toks, list(range(37)))
    lg, (kp, vp, st) = _chunk(params, _pools(), toks, 0, 30, [1, 2, 3, 4], 3)
    assert np.abs(_logp(lg) - want[29]).max() < TOL
    table = jnp.asarray([[1, 2, 3, 4] + [0] * 4] + [[0] * 8] * 2, jnp.int32)
    for p in range(30, 37):
        before = st
        lg, kp, vp, st = jamba.forward(
            C, params, jnp.asarray([[toks[p]], [0], [0]], jnp.int32),
            jnp.asarray([[p], [-1], [-1]], jnp.int32), kp, vp, table,
            jnp.asarray([p + 1, 0, 0]), state=st, slots=jnp.asarray([3, 0, 0]),
            attn_impl=attn_impl)
        assert np.abs(_logp(lg[0, 0]) - want[p]).max() < TOL
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(before)):
            others = [s for s in range(a.shape[1]) if s != 3]
            np.testing.assert_array_equal(np.asarray(a[:, others]), np.asarray(b[:, others]))
            assert not np.array_equal(np.asarray(a[:, 3]), np.asarray(b[:, 3]))


@pytest.mark.parametrize("attn_impl", ["jnp", "pallas"])
def test_ragged_step_with_a_decode_row_and_two_chunks(params, attn_impl, monkeypatch):
    """One flat step: sequence X decodes its 31st token from slot 3, Y's
    first ten tokens start slot 1 (junk in it), Z goes on from its ninth in
    slot 2. Each row's logprobs are the reference's for its own sequence."""
    _interpreted_kernels(monkeypatch)
    x, y, z = _tokens(31, 3), _tokens(10, 5), _tokens(25, 6)
    _, pools = _chunk(params, _pools(), x, 0, 30, [1, 2, 3, 4], 3)
    _, (kp, vp, st) = _chunk(params, pools, z, 0, 9, [5, 6], 2)
    q_lens, T = [1, 10, 16], 32
    md = build_ragged_metadata(q_lens, [30, 0, 9], [31, 10, 25],
                               [[1, 2, 3, 4], [7], [5, 6]], T, q_block=8, max_pages=MP)
    flat = np.zeros(T, np.int32)
    flat[0], flat[1:11], flat[11:27] = x[30], y, z[9:25]
    cap = md["seg_page_table"].shape[0]
    gather = np.zeros(cap, np.int32)
    gather[:3] = md["last_index"]
    seg = np.zeros((3, cap), np.int32)
    seg[:, :3] = [[3, 1, 2], [0, 1, 11], q_lens]
    lg, _, _, st2 = jamba.forward(
        C, params, jnp.asarray(flat[None]), jnp.asarray(md["tok_positions"])[None],
        kp, vp, jnp.asarray(md["tok_page_table"]), jnp.asarray(md["tok_kv_lens"]),
        last_index=jnp.asarray(gather),
        ragged=tuple(jnp.asarray(md[k]) for k in ("seg_page_table", "seg_kv_lens", "meta")),
        state=st, slots=jnp.asarray(seg), attn_impl=attn_impl)
    got = _logp(lg[0])
    for row, (toks, at) in enumerate(((x, 30), (y, 9), (z, 24))):
        want = ref.logprobs_at(MODEL, params, toks, [at])[0]
        assert np.abs(got[row] - want).max() < TOL, row
    for a, b in zip(jax.tree.leaves(st2), jax.tree.leaves(st)):
        np.testing.assert_array_equal(np.asarray(a[:, [0, 4, 5]]), np.asarray(b[:, [0, 4, 5]]))


# -- both kernels, interpreted, against their jnp forms ----------------------


def _ssm_operands(T, d=256, N=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    pool = f(3, 6, N, *ssm.state_shape(d))
    return pool, (f(T, d), jax.nn.softplus(f(T, d)), f(T, N), f(T, N), -jnp.exp(f(N, d)))


def test_ssm_update_kernel_agrees_with_its_jnp_form():
    pool, ops = _ssm_operands(8)
    slots = jnp.asarray([3, 1, 5, 2, 0, 0, 0, 0], jnp.int32)
    live = jnp.arange(8) < 4  # live rows lead; the rest are padding
    fresh = jnp.asarray([0, 1, 0, 0, 0, 0, 0, 0]) != 0
    y0, p0 = ssm.ssm_update_jnp(pool, 1, slots, live, fresh, *ops)
    y1, p1 = ssm.ssm_update(pool, 1, slots, live, fresh, *ops, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(p1, p0, atol=1e-5)
    touched = np.zeros(pool.shape[:2], bool)
    touched[1, [3, 1, 5, 2]] = True
    np.testing.assert_array_equal(np.asarray(p1)[~touched], np.asarray(pool)[~touched])
    assert not np.allclose(np.asarray(p1)[touched], np.asarray(pool)[touched])
    assert float(jnp.abs(y1[4:]).max()) == 0


def test_ssm_scan_kernel_agrees_with_its_jnp_form():
    T = 24  # segments: 1 token, 1 (fresh), 11, 7 (fresh); 4 tokens of padding
    pool, ops = _ssm_operands(T, seed=1)
    seg_of = np.array([0, 1] + [2] * 11 + [3] * 11)
    start, length = np.array([0, 1, 2, 13]), np.array([1, 1, 11, 7])
    slot, fresh = np.array([2, 4, 1, 5]), np.array([0, 1, 0, 1])
    off = np.arange(T) - start[seg_of]
    flags = ssm.scan_flags(jnp.arange(T) < 20, jnp.asarray(off == 0),
                           jnp.asarray(off == length[seg_of] - 1),
                           jnp.asarray(fresh[seg_of] != 0))
    tok_slot = jnp.asarray(slot[seg_of], jnp.int32)
    y0, p0 = ssm.ssm_scan_jnp(pool, 2, tok_slot, flags, *ops)
    y1, p1 = ssm.ssm_scan(pool, 2, tok_slot, flags, *ops, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(p1, p0, atol=1e-5)
    touched = np.zeros(pool.shape[:2], bool)
    touched[2, slot] = True
    np.testing.assert_array_equal(np.asarray(p1)[~touched], np.asarray(pool)[~touched])
    assert float(jnp.abs(y1[20:]).max()) == 0
    # a one-token segment is the update: the two kernels agree
    y2, p2 = ssm.ssm_update(pool, 2, jnp.asarray([2, 4], jnp.int32),
                            jnp.asarray([True, True]), jnp.asarray([False, True]),
                            *(a[:2] for a in ops[:4]), ops[4], interpret=True)
    np.testing.assert_allclose(y2, y1[:2], atol=1e-5)
    np.testing.assert_allclose(p2[2, [2, 4]], p1[2, [2, 4]], atol=1e-5)


# -- through the runner and the engine --------------------------------------


def _engine(monkeypatch, params, **engine_kw):
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    args = worker.parse_args([
        "--model", "tiny-jamba", "--max-batch", "4", "--chunk-size", "16",
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


async def _serve(engine, ids, n_out, cancel_after=None):
    toks, lps = [], []
    ctx = Context()
    payload = {"token_ids": [int(t) for t in ids],
               "sampling": {"temperature": 0.0, "logprobs": 0},
               "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}}
    async for item in engine.generate(payload, ctx):
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


async def test_engine_sizes_the_pool_and_serves_through_every_program(monkeypatch, params):
    engine, runner = _engine(monkeypatch, params)
    try:
        sched = engine.scheduler
        assert runner.side_kind == "state" and runner.ragged_mixed
        assert runner.side_units == sched.side.units == 4 + 1
        assert runner.side_unit_bytes == jamba.state_slot_bytes(C, conv_dtype=jnp.float32)
        assert runner.k_pool.shape[0] == 2  # the attention layers alone
        assert not sched.enable_prefix_cache
        # junk in every slot: nothing a sequence reads before it wrote it
        runner.state = jax.tree.map(lambda a: a + 9.0, runner.state)
        lead = _tokens(12, 10)
        rest = [_tokens(n, 11 + n) for n in (19, 26, 40)]

        async def late(ids):
            await asyncio.sleep(0.05)
            return await _serve(engine, ids, 5)

        got = await asyncio.gather(_serve(engine, lead, 30), *(late(r) for r in rest))
        for ids, (toks, lps) in zip([lead] + rest, got):
            _held_to_reference(params, ids, toks, lps)
        recs = engine.recorder.snapshot()
        assert max(r.state_slots_used for r in recs) >= 2
        assert all(r.state_slots_total == 4 for r in recs)
        assert sum(r.ssm_scan_tokens for r in recs) >= sum(len(r) for r in rest)
        assert sched.side.used == 0 and len(sched.side._free) == 4
        # without logprobs the same drive rides the ragged program
        calls0 = runner.compile_stats()["ragged"]["calls"]

        async def plain(ids, n, wait):
            await asyncio.sleep(wait)
            out = []
            async for item in engine.generate(
                    {"token_ids": [int(t) for t in ids], "sampling": {"temperature": 0.0},
                     "stop": {"max_tokens": n, "stop_ids": [], "ignore_eos": True}}, Context()):
                out += list(item.get("token_ids") or [])
                if item.get("finish_reason"):
                    break
            return out

        outs = await asyncio.gather(plain(lead, 30, 0), *(plain(r, 5, 0.05) for r in rest))
        assert runner.compile_stats()["ragged"]["calls"] > calls0
        assert [o for o in outs] == [g[0] for g in got]
        assert any(r.ragged and r.ssm_scan_segments > r.n_chunks for r in engine.recorder.snapshot())
    finally:
        engine.stop()


async def test_preempted_and_cancelled_sequences_leave_no_state_behind(monkeypatch, params):
    """A sequence preempted mid-decode gives its slot back and, readmitted,
    computes again from position 0; one cancelled mid-decode frees its slot,
    and the next sequence takes that very slot. Both end with the logprobs
    of a fresh run: a slot that kept its old state would not."""
    engine, runner = _engine(monkeypatch, params)
    try:
        sched = engine.scheduler
        a, b = _tokens(14, 20), _tokens(11, 21)
        plan, calls, seen = sched.step_plan, {"n": 0}, {}

        def preempting():
            calls["n"] += 1
            run = [s for s in sched.active if s.state == SeqState.RUNNING]
            if run and run[0].n_generated >= 4 and not seen:
                slot = run[0].side
                # (StepsInFlight while the loop has a dispatch in flight:
                # it commits that and plans again, and this runs again)
                sched._preempt(run[0])
                seen["slot"] = slot
            return plan()

        sched.step_plan = preempting
        toks, lps = await _serve(engine, a, 12)
        assert seen["slot"] > 0 and len(toks) == 12
        _held_to_reference(params, a, toks, lps)
        sched.step_plan = plan
        # cancel mid-decode, then reuse: b lands in the slot a's second run held
        await _serve(engine, a, 30, cancel_after=6)
        for _ in range(200):
            if not sched.active:
                break
            await asyncio.sleep(0.01)
        assert sched.side.used == 0
        freed = sched.side._free[-1]
        assert float(jnp.abs(runner.state["S"][:, freed]).max()) > 0  # a's, stale

        async def watch():
            while not sched.active:
                await asyncio.sleep(0.001)
            return sched.active[0].side

        slot, (toks, lps) = await asyncio.gather(watch(), _serve(engine, b, 8))
        assert slot == freed
        _held_to_reference(params, b, toks, lps)
    finally:
        engine.stop()


def test_every_active_sequence_owns_a_slot_and_gives_it_back():
    from dynamo_tpu.engine.scheduler import Sequence

    sched = Scheduler(PagePool(64, 4), max_batch=2, enable_prefix_cache=False,
                      side=StateSlots(3))  # scratch + one a row

    def seq(i):
        return Sequence(request_id=f"r{i}", prompt=[1, 2, 3], sampling={},
                        stop={"max_tokens": 4})

    for i in range(3):
        sched.add(seq(i))
    sched.step_plan()
    assert [s.side for s in sched.active] == [1, 2]
    assert len(sched.waiting) == 1 and sched.waiting[0].side is None
    assert sched.side.used == 2
    sched.abort("r0")
    sched.step_plan()
    assert sorted(s.side for s in sched.active) == [1, 2]
    assert not sched.waiting


def test_the_runner_names_the_decode_kernels_page_routine(params, caplog):
    """A static fact of a worker, in `runner ready` and /debug/device, from the
    one decision the kernel's wrapper makes: every dense pool that is not
    `by_rows` is the tile routine (one KV head its one-head case); the jnp
    gather has none; a model whose window layers keep a pool of their own
    names a routine a kind (its window layers carry a sink). The ragged
    (mixed-step) kernel's routine beside it, from that kernel's own decision:
    the tile routine for every dense pool but G = 1 at 32 KV heads or more,
    the float32 product a head there and for an int8 pool."""
    import logging

    kw = dict(num_pages=8, page_size=4, params=params, dtype=jnp.float32)
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.engine.runner"):
        on_kernel = ModelRunner(C, attn_impl="pallas", **kw)
    assert on_kernel.device_report()["decode_page_routine"] == "by_tiles"
    assert "decode_page_routine=by_tiles" in caplog.text
    assert on_kernel.device_report()["ragged_page_routine"] == "by_tiles"
    assert "ragged_page_routine=by_tiles" in caplog.text
    on_gather = ModelRunner(C, **kw).device_report()
    assert on_gather["decode_page_routine"] is on_gather["ragged_page_routine"] is None
    wide = ModelRunner(get_config("tiny"), num_pages=8, page_size=4, attn_impl="pallas")
    assert wide.device_report()["decode_page_routine"] == "by_tiles"
    mha = get_config("tiny").with_(n_heads=8, n_kv_heads=8)
    by_rows = ModelRunner(mha, num_pages=8, page_size=4, attn_impl="pallas",
                          dtype=jnp.float32)
    assert by_rows.device_report()["decode_page_routine"] == "by_rows"
    # the ragged rule is that kernel's own: G = 1 keeps the product a head
    # from 32 KV heads on, whatever the pool's dtype
    assert by_rows.device_report()["ragged_page_routine"] == "by_tiles"
    mha32 = ModelRunner(get_config("tiny").with_(n_heads=32, n_kv_heads=32),
                        num_pages=8, page_size=4, attn_impl="pallas")
    assert mha32.device_report()["ragged_page_routine"] == "by_heads"
    int8 = ModelRunner(get_config("tiny"), num_pages=8, page_size=4,
                       attn_impl="pallas", kv_quantize="int8")
    assert int8.device_report()["decode_page_routine"] == "by_heads"
    assert int8.device_report()["ragged_page_routine"] == "by_heads"
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.engine.runner"):
        two_kinds = ModelRunner(get_config("tiny-mimo"), num_pages=8, page_size=4,
                                attn_impl="pallas")
    assert two_kinds.device_report()["decode_page_routine"] == {
        "global": "by_tiles", "window": "by_tiles"}
    assert "decode_page_routine={'global': 'by_tiles', 'window': 'by_tiles'}" in caplog.text
    assert two_kinds.device_report()["ragged_page_routine"] == {
        "global": "by_tiles", "window": "by_tiles"}
    assert "ragged_page_routine={'global': 'by_tiles', 'window': 'by_tiles'}" in caplog.text


def test_a_pool_with_fewer_slots_than_rows_is_refused():
    with pytest.raises(ValueError, match="3 state slots.*max_batch 8"):
        Scheduler(PagePool(64, 4), max_batch=8, enable_prefix_cache=False,
                  side=StateSlots(3))


def test_a_step_on_a_pool_nobody_sized_raises(params):
    runner = ModelRunner(
        C, num_pages=16, page_size=4, max_pages_per_seq=8, decode_buckets=(2,),
        prefill_buckets=(8,), ragged_buckets=(8,), params=params, dtype=jnp.float32)
    with pytest.raises(RuntimeError, match="ensure_side_cache"):
        runner.prefill([1, 2, 3], 0, [1], 0)
    assert runner.ensure_side_cache(3) == 3 and runner.side_units == 3
    assert runner.state["S"].dtype == jnp.float32
    runner.prefill([1, 2, 3], 0, [1], 0, side=1)


# -- what a state-holding model refuses, in words ----------------------------


def test_every_path_that_cannot_carry_state_refuses_in_words(monkeypatch, params):
    words = "state-space layers"
    with pytest.raises(ValueError, match="matches no prefix"):
        Scheduler(PagePool(8, 4), max_batch=2, enable_prefix_cache=True, side=StateSlots(3))
    assert words in StateSlots.no_prefix
    with pytest.raises(ValueError, match="tier demotion.*" + words):
        _engine(monkeypatch, params, host_kv_blocks=8)
    with pytest.raises(ValueError, match="speculative decoding.*" + words):
        _engine(monkeypatch, params, spec_ngram=True)
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
            from dynamo_tpu.engine.scheduler import Sequence

            engine.scheduler.admit_with_kv(Sequence(
                request_id="d", prompt=[1, 2], sampling={}, stop={}))

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
    finally:
        engine.stop()
    with pytest.raises(NotImplementedError, match="models/jamba.forward"):
        llama.forward(C, params, jnp.zeros((1, 1), jnp.int32),
                      jnp.zeros((1, 1), jnp.int32), *[None] * 4)
    with pytest.raises(NotImplementedError, match="not sharded"):
        from dynamo_tpu.parallel.mesh import MeshConfig

        ModelRunner(C, MeshConfig(model=2), num_pages=8, page_size=4, params=params)
