"""Ling-3.0's hybrid decoder (models/ling.py): the program against the plain
reference (benchmark/reference/ling3_decoder.py; logits, not tokens) on every
path a sequence takes: one prefill, chunks, the decode loop through slot and
latent pages, rows of unequal length in one step, a mixed iteration as two
dispatches, preemption, cancellation and reuse of a slot. Seeded random
weights, small sizes, float32 on the CPU (so the tolerance is float32
rounding: 2e-4 on a logprob where two float32 programs order their sums
differently; the reference FOLLOWS the program's picks, so a router's tie is
no difference)."""

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
from dynamo_tpu.engine.kv_pool import PagePool
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.scheduler import Scheduler, SeqState
from dynamo_tpu.engine.side_cache import StateSlots
from dynamo_tpu.engine.weights import load_hf_checkpoint
from dynamo_tpu.models import ling, llama
from dynamo_tpu.models.config import ModelConfig, get_config
from dynamo_tpu.models.toolkit import make_kv_pool
from dynamo_tpu.ops import kda
from dynamo_tpu.runtime.context import Context

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
NP, PS, MP = 24, 8, 8


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/ling3_decoder.py", "_ling3_reference")
C = get_config("tiny-ling")


def _model(c):
    return {f.name: getattr(c, f.name) for f in dataclasses.fields(c)
            if isinstance(getattr(c, f.name), (bool, int, float, str))}


MODEL = _model(C)


def _params(c=C, seed=0):
    """The tree with the fills made random too (the gate's, the norms), as a
    checkpoint has them."""
    params = llama.init_params(c, jax.random.PRNGKey(seed), jnp.float32)
    rng = np.random.default_rng(seed + 1)

    def rnd(a, s=0.3):
        return a + jnp.asarray(rng.normal(size=a.shape) * s, a.dtype)

    for n in ("A_log", "dt_bias", "o_norm"):
        params["kda"][n] = rnd(params["kda"][n])
    params["mla"]["kv_norm"] = rnd(params["mla"]["kv_norm"])
    for stack in [n for n in ("layers", "layers_dense") if n in params]:
        for n in ("attn_norm", "mlp_norm"):
            params[stack][n] = rnd(params[stack][n])
    params["layers"]["router_bias"] = rnd(params["layers"]["router_bias"], 0.05)
    params["norm_f"] = rnd(params["norm_f"])
    return params


@pytest.fixture(scope="module")
def params():
    return _params()


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, C.vocab_size, size=n)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def _want(params, toks, picks, model=MODEL):
    """The reference's rows for `toks`, following the program's picks
    [L_moe, S, k] (a tie of the router's is then no difference), and the
    picks' need, which a float32 program keeps at rounding."""
    logp, need = ref.follow_at(model, params, np.asarray(toks), list(range(len(toks))),
                               np.moveaxis(np.asarray(picks), 0, 1))
    assert float(need.max()) < 1e-4
    return logp


def _pools(c=C, slots=6, poison=7.0):
    """Latent pages and a state pool whose every slot holds junk: a
    sequence's first token must not read what its slot held."""
    kp, vp = make_kv_pool(c, NP, PS, jnp.float32)
    state = ling.make_state_pool(c, slots, conv_dtype=jnp.float32)
    return kp, vp, jax.tree.map(lambda a: a + poison, state)


def _chunk(params, pools, toks, start, n, table, slot, S=32, c=C, impl="jnp"):
    """One prefill chunk of `n` tokens from `start` at bucket S:
    (every position's logits [n, V], picks [L_moe, n, k], the pools)."""
    t = np.zeros((1, S), np.int32)
    t[0, :n] = toks[start:start + n]
    p = np.full((1, S), -1, np.int32)
    p[0, :n] = np.arange(start, start + n)
    lg, kp, vp, sel, _, st = ling.forward(
        c, params, jnp.asarray(t), jnp.asarray(p), pools[0], pools[1],
        jnp.asarray([table + [0] * (MP - len(table))], jnp.int32),
        jnp.asarray([start + n]), state=pools[2], slots=jnp.asarray([slot]),
        attn_impl=impl)
    return lg[0, :n], sel[:, 0, :n], (kp, vp, st)


# -- the program against the reference ---------------------------------------


def test_the_reference_follows_its_own_picks_bit_for_bit(params):
    toks = _tokens(29, 2)
    at = list(range(29))
    own = ref.logprobs_at(MODEL, params, toks, at)
    logp, need = ref.follow_at(MODEL, params, toks, at, ref.own_picks(MODEL, params, toks))
    np.testing.assert_array_equal(logp, own)
    assert not need.any()


def test_one_prefill_agrees_with_the_reference(params):
    toks = _tokens(30, 3)
    lg, sel, _ = _chunk(params, _pools(), toks, 0, 30, [1, 2, 3, 4], 3)
    assert np.abs(_logp(lg) - _want(params, toks, sel)).max() < TOL


@pytest.mark.parametrize("n_layers, nd", [(9, 1), (12, 2), (7, 0), (4, 1)])
def test_depths_that_scan_whole_periods_and_that_do_not(n_layers, nd):
    """9 layers: a period walked run by run and two scanned; 12 with two
    dense layers: three scanned; 7: a KDA layer past the last whole period;
    4: one MLA layer and no scan."""
    c = C.with_(n_layers=n_layers, n_dense_layers=nd)
    p = _params(c, seed=n_layers)
    toks = _tokens(21, 8)
    kp, vp = make_kv_pool(c, NP, PS, jnp.float32)
    assert kp.shape[0] == c.kv_layers == n_layers // 3
    lg, sel, _ = _chunk(p, _pools(c), toks, 0, 21, [1, 2, 3], 2, c=c)
    assert sel.shape[0] == n_layers - nd
    assert np.abs(_logp(lg) - _want(p, toks, sel, _model(c))).max() < TOL


@pytest.mark.parametrize("sizes", [[13, 17], [5, 6, 7, 8, 4], [1, 2, 27]])
def test_a_prompt_in_any_chunks_is_the_reference(params, sizes):
    """State and convolution inputs carried across chunk ends (a chunk
    shorter than the convolution's reach among them): every position's
    logprobs are the whole pass's."""
    toks = _tokens(30, 4)
    pools, start, rows, picks = _pools(), 0, [], []
    for n in sizes:
        lg, sel, pools = _chunk(params, pools, toks, start, n, [1, 2, 3, 4], 3)
        rows.append(lg)
        picks.append(sel)
        start += n
    want = _want(params, toks, jnp.concatenate(picks, axis=1))
    assert np.abs(_logp(jnp.concatenate(rows)) - want).max() < TOL


def _interpreted_kernels(monkeypatch):
    """attn_impl="pallas" on the CPU: the delta-rule kernels, latent
    attention's and the routed experts' work-list kernel in interpret mode."""
    from dynamo_tpu.ops import mla_attention as mla_ops
    from dynamo_tpu.ops import moe_experts

    for mod, name in ((kda, "kda_update"), (kda, "kda_chunk"),
                      (mla_ops, "decode_mla_attention"), (mla_ops, "prefill_mla_attention"),
                      (moe_experts, "routed_experts")):
        monkeypatch.setattr(mod, name, functools.partial(getattr(mod, name), interpret=True))


@pytest.mark.parametrize("attn_impl", ["jnp", "pallas"])
def test_prefill_then_decode_rows_of_unequal_length(params, attn_impl, monkeypatch):
    """Two sequences prefilled (26 and 11 tokens), then decoded side by side
    beside a padding row: every logprob is the reference's full pass of its
    sequence, and the padding row (the scratch slot, no position) changes no
    slot."""
    _interpreted_kernels(monkeypatch)
    a, b = _tokens(33, 5), _tokens(18, 6)
    pools = _pools()
    lg_a, sel_a, pools = _chunk(params, pools, a, 0, 26, [1, 2, 3, 4, 5], 3, impl=attn_impl)
    lg_b, sel_b, pools = _chunk(params, pools, b, 0, 11, [6, 7, 8], 5, impl=attn_impl)
    kp, vp, st = pools
    table = jnp.asarray([[1, 2, 3, 4, 5, 0, 0, 0], [6, 7, 8, 0, 0, 0, 0, 0], [0] * 8], jnp.int32)
    rows_a, rows_b, picks_a, picks_b = [lg_a], [lg_b], [sel_a], [sel_b]
    for j in range(7):
        before = st
        lg, kp, vp, sel, _, st = ling.forward(
            C, params, jnp.asarray([[a[26 + j]], [b[11 + j]], [0]], jnp.int32),
            jnp.asarray([[26 + j], [11 + j], [-1]], jnp.int32), kp, vp, table,
            jnp.asarray([27 + j, 12 + j, 0]), state=st, slots=jnp.asarray([3, 5, 0]),
            attn_impl=attn_impl)
        rows_a.append(lg[0])
        rows_b.append(lg[1])
        picks_a.append(sel[:, 0])
        picks_b.append(sel[:, 1])
        for x, y in zip(jax.tree.leaves(st), jax.tree.leaves(before)):
            others = [s for s in range(x.shape[1]) if s not in (3, 5)]
            np.testing.assert_array_equal(np.asarray(x[:, others]), np.asarray(y[:, others]))
            assert not np.array_equal(np.asarray(x[:, 3]), np.asarray(y[:, 3]))
    for toks, rows, picks in ((a, rows_a, picks_a), (b, rows_b, picks_b)):
        want = _want(params, toks, jnp.concatenate(picks, axis=1))
        assert np.abs(_logp(jnp.concatenate(rows)) - want).max() < TOL


def test_a_slot_a_new_sequence_takes_leaks_nothing(params):
    """The same prompt into a slot that held another sequence's state and
    into one that held junk: the same logits bit for bit."""
    a, b = _tokens(20, 7), _tokens(14, 9)
    _, _, used = _chunk(params, _pools(), a, 0, 20, [1, 2, 3], 4)
    lg1, _, _ = _chunk(params, used, b, 0, 14, [4, 5], 4)
    lg2, _, _ = _chunk(params, _pools(poison=-3.0), b, 0, 14, [4, 5], 4)
    np.testing.assert_array_equal(np.asarray(lg1), np.asarray(lg2))


def test_the_sixteen_shares_add_up_to_the_uncut_layer(params):
    """One expert layer's output on the same input: the routed terms of the
    four shares (16 experts, 4 held each), the shared expert counted once,
    add up to what the layer gives with every expert held; in the program
    (models/moe.py under this family's configuration) and in the reference."""
    from dynamo_tpu.models.moe import _moe_block

    whole = C.with_(n_experts_held=0, expert_first=0)
    full = _params(whole, seed=3)
    lp = jax.tree.map(lambda a: a[1], full["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, C.dim))
    y_all, sel_all, _ = _moe_block(whole, lp, x)
    shared, _, _ = _moe_block(
        whole, {**lp, **{k: jnp.zeros_like(lp[k]) for k in ("we_gate", "we_up", "we_down")}}, x)
    routed = 0.0
    for first in range(0, 16, 4):
        c = C.with_(n_experts_held=4, expert_first=first)
        mine = {**lp, **{k: lp[k][first:first + 4] for k in ("we_gate", "we_up", "we_down")}}
        y, sel, _ = _moe_block(c, mine, x)
        np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel_all))
        routed = routed + (y - shared)
        m = {**_model(c)}
        y_ref, _, _ = ref._experts(x[0], mine, m, sel[0], True)
        assert np.abs(np.asarray(y_ref) - np.asarray(y[0])).max() < 1e-5
    assert np.abs(np.asarray(routed + shared) - np.asarray(y_all)).max() < 1e-5


# -- through the engine --------------------------------------------------------


def _engine(monkeypatch, params, **engine_kw):
    args = worker.parse_args([
        "--model", "tiny-ling", "--max-batch", "4", "--chunk-size", "16",
        "--mixed-prefill-tokens", "12", "--mixed-prefill-seqs", "1",
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
    toks, lps, routed = [], [], []
    sampling = {"temperature": 0.0, "routed_experts": True,
                **({"logprobs": 0} if logprobs else {})}
    payload = {"token_ids": [int(t) for t in ids], "sampling": sampling,
               "stop": {"max_tokens": n_out, "stop_ids": [], "ignore_eos": True}}
    async for item in engine.generate(payload, Context()):
        toks += list(item.get("token_ids") or [])
        lps += [e["logprob"] for e in item.get("logprobs") or []]
        routed += (item.get("routed_experts") or {}).get("ids", [])
        if cancel_after is not None and len(toks) >= cancel_after:
            return toks, lps, routed  # leaving the stream aborts the request
        if item.get("finish_reason"):
            assert item["finish_reason"] != "error", item
            break
    return toks, lps, routed


def _held_to_reference(params, ids, toks, lps, routed):
    seq = np.asarray(list(ids) + toks[:-1], np.int32)
    at = list(range(len(ids) - 1, len(seq)))
    want, need = ref.follow_at(MODEL, params, seq, at, np.asarray(routed, np.int32))
    assert float(need.max()) < 1e-4
    mine = want[np.arange(len(toks)), toks]
    if lps:
        assert np.abs(mine - np.asarray(lps)).max() < TOL
    assert float((want.max(-1) - mine).max()) < TOL


async def test_engine_serves_a_mixed_iteration_as_two_dispatches(monkeypatch, params):
    engine, runner = _engine(monkeypatch, params)
    try:
        sched = engine.scheduler
        assert runner.side_kind == "state" and not runner.fuses_mixed
        assert not runner.ragged_mixed and not engine.fused_mixed
        assert runner.side_units == sched.side.units == 4 + 1 and sched.side.kda
        assert runner.side_unit_bytes == ling.state_slot_bytes(C, conv_dtype=jnp.float32)
        assert runner.k_pool.shape[0] == 2  # the MLA layers alone
        assert not sched.enable_prefix_cache
        # junk in every slot: nothing a sequence reads before it wrote it
        runner.state = jax.tree.map(lambda a: a + 9.0, runner.state)
        lead = _tokens(12, 10)
        rest = [_tokens(n, 11 + n) for n in (19, 26, 40)]

        async def late(ids, **kw):
            await asyncio.sleep(0.05)
            return await _serve(engine, ids, 5, **kw)

        got = await asyncio.gather(_serve(engine, lead, 30), *(late(r) for r in rest))
        for ids, out in zip([lead] + rest, got):
            _held_to_reference(params, ids, *out)
        recs = engine.recorder.snapshot()
        assert max(r.state_slots_used for r in recs) >= 2
        assert all(r.state_slots_total == 4 for r in recs)
        assert sum(r.kda_chunk_tokens for r in recs) == 12 + sum(len(r) for r in rest)
        assert sum(r.kda_chunk_segments for r in recs) == sum(r.n_chunks for r in recs)
        assert sum(r.kda_update_rows for r in recs) == sum(
            r.decode_seqs * r.decode_steps for r in recs)
        assert not any(r.ssm_scan_tokens for r in recs)
        # chunks rode beside live decode rows, each iteration two dispatches
        assert any(r.n_chunks and r.decode_seqs for r in recs) and not any(r.fused for r in recs)
        assert sched.side.used == 0 and len(sched.side._free) == 4
        stats = runner.compile_stats()
        assert stats["ragged"]["calls"] == stats["mixed"]["calls"] == 0
        # without logprobs: the same drive, the same tokens
        outs = await asyncio.gather(_serve(engine, lead, 30, logprobs=False),
                                    *(late(r, logprobs=False) for r in rest))
        assert [o[0] for o in outs] == [g[0] for g in got]
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
        plan, seen = sched.step_plan, {}

        def preempting():
            run = [s for s in sched.active if s.state == SeqState.RUNNING]
            if run and run[0].n_generated >= 4 and not seen:
                slot = run[0].side
                sched._preempt(run[0])
                seen["slot"] = slot
            return plan()

        sched.step_plan = preempting
        toks, lps, routed = await _serve(engine, a, 12)
        assert seen["slot"] > 0 and len(toks) == 12
        # (the stream's picks restart with the recomputed prompt: the check
        # takes the logprobs, and the tokens as the reference's own best)
        seq = np.asarray(list(a) + toks[:-1], np.int32)
        want = ref.logprobs_at(MODEL, params, seq, list(range(len(a) - 1, len(seq))))
        assert np.abs(want[np.arange(12), toks] - np.asarray(lps)).max() < TOL
        sched.step_plan = plan
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

        slot, out = await asyncio.gather(watch(), _serve(engine, b, 8))
        assert slot == freed
        _held_to_reference(params, b, *out)
    finally:
        engine.stop()


def test_every_path_that_cannot_carry_state_refuses_in_words(monkeypatch, params, tmp_path):
    words = "state-space layers"
    with pytest.raises(ValueError, match="matches no prefix"):
        Scheduler(PagePool(8, 4), max_batch=2, enable_prefix_cache=True, side=StateSlots(3, kda=True))
    with pytest.raises(ValueError, match="tier demotion.*" + words):
        _engine(monkeypatch, params, host_kv_blocks=8)
    with pytest.raises(ValueError, match="speculative decoding.*" + words):
        _engine(monkeypatch, params, spec_ngram=True)
    with pytest.raises(NotImplementedError, match="kv-quantize.*" + words):
        ModelRunner(C, num_pages=8, page_size=4, params=params, kv_quantize="int8")
    with pytest.raises(NotImplementedError, match="not sharded"):
        from dynamo_tpu.parallel.mesh import MeshConfig

        ModelRunner(C, MeshConfig(model=2), num_pages=8, page_size=4, params=params)
    with pytest.raises(NotImplementedError, match="draft model.*" + words):
        ModelRunner(C, num_pages=8, page_size=4, params=params, draft_config=get_config("tiny"))
    with pytest.raises(NotImplementedError, match="no checkpoint loader.*KDA"):
        load_hf_checkpoint(str(tmp_path), C)
    with pytest.raises(NotImplementedError, match="models/ling.forward"):
        llama.forward(C, params, jnp.zeros((1, 1), jnp.int32),
                      jnp.zeros((1, 1), jnp.int32), *[None] * 4)
    with pytest.raises(NotImplementedError, match="one at a time"):
        kp, vp, st = _pools()
        ling.forward(C, params, jnp.zeros((2, 8), jnp.int32), jnp.zeros((2, 8), jnp.int32),
                     kp, vp, jnp.zeros((2, MP), jnp.int32), jnp.asarray([8, 8]), state=st)
    engine, runner = _engine(monkeypatch, params)
    try:
        for call, what in (
                (lambda: runner.export_pages([1]), "KV export"),
                (lambda: runner.import_pages([1], 0, {}), "KV import"),
                (lambda: runner.verify_spec([1], [0], [[1]], [[2]], {}, 1),
                 "speculative verify")):
            with pytest.raises(NotImplementedError, match=what + ".*" + words):
                call()
    finally:
        engine.stop()


@pytest.mark.parametrize("bad, match", [
    (dict(kda_layer_period=1), "kda_layer_period > 1"),
    (dict(kda_head_dim=0), "kda_head_dim > 0"),
    (dict(tie_embeddings=True), "Ling-3.0's"),
    (dict(sliding_window=8), "Ling-3.0's"),
    (dict(kda_gate_lower=1.0), "kda_gate_lower < 0"),
])
def test_a_configuration_the_family_does_not_have_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        C.with_(**bad)
    with pytest.raises(ValueError, match="state kda_layer_period"):
        ModelConfig(kda_head_dim=16)


def test_the_gates_fills_give_the_stated_half_lives():
    a_log, dt_bias = ling.gate_fills(C)
    g = C.kda_gate_lower / (1 + np.exp(-np.exp(a_log)[:, None] * dt_bias.reshape(C.n_heads, -1)))
    half = np.log(2) / -g
    np.testing.assert_allclose(half[:, 0], ling.HALF_LIFE[0], rtol=1e-3)
    np.testing.assert_allclose(half[:, -1], ling.HALF_LIFE[1], rtol=1e-3)
    assert np.all(np.diff(half, axis=1) > 0)
