"""Speculative decoding: losslessness and engine integration.

The two greedy tests pin the strongest property: spec output must be
token-identical to plain greedy decoding of the target model, whether the
draft agrees (all accepts) or is garbage (constant rejections). The bulk
test checks the accept/resample math preserves the target distribution for
temperature sampling."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.spec_decode import accept_and_finalize
from dynamo_tpu.models.config import get_config
from dynamo_tpu.runtime.context import Context


def _runner(draft_seed=None, spec_gamma=3):
    cfg = get_config("tiny")
    kw = {}
    if draft_seed is not None:
        import dynamo_tpu.models.llama as llama

        kw = dict(
            draft_config=cfg,
            draft_params=llama.init_params(cfg, jax.random.PRNGKey(draft_seed)),
            spec_gamma=spec_gamma,
        )
    return ModelRunner(
        cfg,
        num_pages=96,
        page_size=4,
        max_pages_per_seq=24,
        decode_buckets=(1, 2, 4),
        prefill_buckets=(8, 16),
        seed=7,
        **kw,
    )


async def _generate(runner, prompt, n=12, temperature=0.0, decode_steps=8):
    engine = InferenceEngine(runner, max_batch=4, chunk_size=16, decode_steps=decode_steps)
    engine.start()
    try:
        toks = []
        req = {
            "token_ids": prompt,
            "sampling": {"temperature": temperature, "seed": 11},
            "stop": {"max_tokens": n, "stop_ids": []},
        }
        async for item in engine.generate(req, Context()):
            toks.extend(item["token_ids"])
            if item["finish_reason"]:
                break
        return toks
    finally:
        engine.stop()


async def test_spec_greedy_matches_plain_with_perfect_draft():
    """Draft == target (same seed): every proposal accepted; output must
    equal plain greedy decoding."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    plain = await _generate(_runner(), prompt)
    spec = await _generate(_runner(draft_seed=7), prompt)
    assert plain == spec


async def test_spec_greedy_matches_plain_with_garbage_draft():
    """Draft with unrelated random weights: rejections happen, but greedy
    output must STILL equal the target's plain greedy decode."""
    prompt = [2, 7, 1, 8, 2, 8, 1, 8]
    plain = await _generate(_runner(), prompt)
    spec = await _generate(_runner(draft_seed=99), prompt)
    assert plain == spec


async def test_spec_respects_max_tokens_below_gamma():
    """max_tokens < gamma+1 forces the non-spec fallback path; both paths
    must agree and respect the budget."""
    prompt = [5, 5, 5, 5]
    plain = await _generate(_runner(), prompt, n=2)
    spec = await _generate(_runner(draft_seed=99), prompt, n=2)
    assert plain == spec and len(spec) == 2


async def test_spec_sampled_runs():
    """Temperature sampling smoke test through the engine spec path."""
    toks = await _generate(_runner(draft_seed=42), [1, 2, 3, 4], n=8, temperature=0.9)
    assert len(toks) == 8


def test_accept_math_preserves_target_distribution():
    """Bulk synthetic check of accept_and_finalize: the marginal of the
    first emitted token must match the target distribution p regardless of
    the draft distribution q (the spec-decoding losslessness theorem)."""
    rng = np.random.default_rng(0)
    B, g, K = 40000, 2, 4
    p = np.asarray([0.55, 0.25, 0.15, 0.05], np.float32)
    q = np.asarray([0.10, 0.20, 0.30, 0.40], np.float32)  # deliberately bad
    ids = np.arange(K, dtype=np.int32)

    # drafts sampled from q independently per position
    drafts = rng.choice(K, size=(B, g), p=q).astype(np.int32)
    q_d = q[drafts]
    t_idx = np.broadcast_to(ids, (B, g + 1, K)).copy()
    t_probs = np.broadcast_to(p, (B, g + 1, K)).copy()
    q_on_t = np.broadcast_to(q, (B, g, K)).copy()

    sampling = SamplingParams.make(
        temperature=[1.0] * B, top_k=[0] * B, top_p=[1.0] * B,
        seeds=rng.integers(0, 1 << 31, B).tolist(),
    )
    out, counts = jax.jit(accept_and_finalize)(
        jnp.asarray(drafts), jnp.asarray(q_d), jnp.asarray(q_on_t),
        jnp.asarray(t_idx), jnp.asarray(t_probs), sampling, jnp.int32(0),
    )
    out = np.asarray(out)
    counts = np.asarray(counts)
    assert counts.min() >= 1 and counts.max() <= g + 1

    first = out[:, 0]
    emp = np.bincount(first, minlength=K) / B
    l1 = np.abs(emp - p).sum()
    assert l1 < 0.02, (emp, p, l1)


# =========================================================================
# Ragged-verify speculation (--spec-ngram): drafting, accept math, engine
# integration, KV lineage, scheduler budgets, billing, and observability.
# =========================================================================

import hashlib
import logging

from dynamo_tpu.engine.ngram_draft import accept_deterministic, propose
from dynamo_tpu.engine.scheduler import Scheduler, Sequence
from dynamo_tpu.engine.kv_pool import PagePool
from dynamo_tpu.mocker.sim import SimRunner, SimTiming


# -- n-gram proposal --------------------------------------------------------


def test_ngram_propose_longest_suffix_wins():
    # suffix [7, 8] occurs earlier; the 4 tokens after it are the draft
    toks = [1, 7, 8, 5, 6, 2, 3, 7, 8]
    assert propose(toks, 4) == [5, 6, 2, 3]


def test_ngram_propose_most_recent_occurrence_wins():
    # [5] occurs twice; the RIGHTMOST earlier occurrence supplies the draft
    toks = [5, 1, 5, 2, 9, 5]
    assert propose(toks, 2) == [2, 9]


def test_ngram_propose_no_match_and_bounds():
    assert propose([1, 2, 3, 4], 4) == []  # no repeated suffix
    assert propose([1, 1], 0) == []  # k=0
    assert propose([], 4) == []
    # draft truncated to what follows the match
    assert propose([9, 4, 9], 4) == [4, 9]
    # window excludes matches older than `window` tokens
    assert propose([7, 3] + [1, 2] * 6, 2, window=4) == [1, 2]
    assert propose([7, 3, 7] + list(range(100, 120)), 2, window=8) == []


# -- deterministic accept == one-hot-q accept_and_finalize ------------------


def test_accept_deterministic_first_mismatch_and_bonus():
    assert accept_deterministic([5, 6, 7], [5, 6, 7, 9]) == [5, 6, 7, 9]
    assert accept_deterministic([5, 6, 7], [5, 4, 0, 9]) == [5, 4]
    assert accept_deterministic([5], [2, 3]) == [2]
    assert accept_deterministic([], [3]) == [3]


def test_accept_deterministic_equals_onehot_accept_and_finalize():
    """With BOTH p and q one-hot, accept_and_finalize is fully
    deterministic — its output must equal accept_deterministic fed the
    target's argmax samples, for every draft/target combination."""
    g, K = 3, 4
    rng = np.random.default_rng(3)
    for _ in range(50):
        draft = rng.integers(0, K, g).astype(np.int32)
        target = rng.integers(0, K, g + 1).astype(np.int32)  # argmax stream
        t_idx = np.broadcast_to(np.arange(K, dtype=np.int32),
                                (1, g + 1, K)).copy()
        t_probs = np.zeros((1, g + 1, K), np.float32)
        t_probs[0, np.arange(g + 1), target] = 1.0
        q_on_t = np.zeros((1, g, K), np.float32)
        q_on_t[0, np.arange(g), draft] = 1.0
        sampling = SamplingParams.make(
            temperature=[1.0], top_k=[0], top_p=[1.0], seeds=[17])
        out, counts = accept_and_finalize(
            jnp.asarray(draft[None]), jnp.ones((1, g), jnp.float32),
            jnp.asarray(q_on_t), jnp.asarray(t_idx), jnp.asarray(t_probs),
            sampling, jnp.int32(0),
        )
        want = accept_deterministic(list(draft), list(target))
        got = list(np.asarray(out)[0, : int(counts[0])])
        assert got == want, (draft, target, got, want)


def test_accept_deterministic_count_distribution_matches_theory():
    """Bulk check: with iid target samples, the accepted-count law is the
    geometric law accept_and_finalize realizes under one-hot q."""
    rng = np.random.default_rng(5)
    B, g, K = 20000, 3, 4
    p = np.asarray([0.55, 0.25, 0.15, 0.05])
    drafts = rng.integers(0, K, (B, g))
    samples = rng.choice(K, size=(B, g + 1), p=p)
    counts = np.asarray([
        len(accept_deterministic(list(drafts[i]), list(samples[i])))
        for i in range(B)
    ])
    m = float((p * p).sum())  # P[sample == draft] for draft ~ uniform? no:
    # drafts here are uniform, so match prob per position is mean(p) = 1/K
    m = 1.0 / K
    want = np.asarray([
        (1 - m), m * (1 - m), m * m * (1 - m), m ** 3
    ])
    emp = np.bincount(counts - 1, minlength=g + 1) / B
    assert np.abs(emp - want).sum() < 0.03, (emp, want)


# -- mocker engine: byte identity + stats -----------------------------------


def _sim_engine(spec=False, rate=None, k=4, decode_steps=4,
                mixed_tokens=64, speed=0.0, recorder_size=0):
    runner = SimRunner(num_pages=512, page_size=4, max_pages_per_seq=64,
                       timing=SimTiming(speed=speed),
                       spec_accept_rate=rate)
    engine = InferenceEngine(
        runner, max_batch=8, chunk_size=16, decode_steps=decode_steps,
        mixed_prefill_tokens=mixed_tokens, spec_ngram=spec, spec_k=k,
        recorder_size=recorder_size,
    )
    return runner, engine


async def _sim_collect(engine, prompt, n=24, temperature=0.0,
                       extras=None, seed=11):
    toks = []
    req = {"token_ids": prompt,
           "sampling": dict({"temperature": temperature, "seed": seed},
                            **(extras or {})),
           "stop": {"max_tokens": n, "stop_ids": []}}
    async for item in engine.generate(req, Context()):
        assert item.get("finish_reason") != "error", item
        toks.extend(item["token_ids"])
        if item["finish_reason"]:
            break
    return toks


def _sha(streams):
    h = hashlib.sha256()
    for s in streams:
        h.update(np.asarray(s, np.int64).tobytes() + b"|")
    return h.hexdigest()


async def test_sim_spec_greedy_byte_identity_matrix():
    """Greedy output must be byte-identical (sha256) to non-spec decode
    across oracle accept rates and the n-gram drafter."""
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6] * 4,
               [2, 7] * 10, [1, 2, 3, 4, 5] * 5]

    async def run(spec, rate):
        _, e = _sim_engine(spec, rate)
        e.start()
        try:
            return await asyncio.gather(
                *[_sim_collect(e, p) for p in prompts]), e.spec_stats
        finally:
            e.stop()

    base, _ = await run(False, None)
    want = _sha(base)
    for rate in (0.0, 0.5, 0.9, None):  # None = n-gram lookup drafting
        outs, st = await run(True, rate)
        assert _sha(outs) == want, (rate, base, outs)
        if rate is not None:
            assert st["verify_iters"] > 0, st  # speculation engaged


async def test_sim_spec_kv_pool_and_hash_lineage_match_plain():
    """KV commit/rollback: after identical traffic, the page pool's
    free/cached/hash registries must be indistinguishable spec-on vs
    spec-off — rejected drafts leak no pages and corrupt no prefix
    hashes — and a follow-up prompt must still prefix-hit identically."""
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6] * 4, [2, 7] * 10]

    async def run(spec):
        r, e = _sim_engine(spec, 0.7)
        e.start()
        try:
            await asyncio.gather(*[_sim_collect(e, p) for p in prompts])
            follow = await _sim_collect(e, prompts[0][:16] + [8, 8])
        finally:
            e.stop()
        pool = e.scheduler.pool
        state = (sorted(pool.free), sorted(pool.cached),
                 sorted(pool.by_hash.keys()), pool.n_free)
        return state, follow

    plain, follow_plain = await run(False)
    spec, follow_spec = await run(True)
    assert plain == spec
    assert follow_plain == follow_spec


async def test_sim_spec_extras_pause_warns_once_and_stays_correct(caplog):
    """Satellite: a request whose sampling needs logprobs/penalties pauses
    speculation batch-wide with EXACTLY ONE warning per request, and
    every stream stays byte-identical to plain decoding."""
    prompts = [[5, 6] * 8, [1, 2, 3] * 6]

    async def run(spec, extras):
        _, e = _sim_engine(spec, 0.7)
        e.start()
        try:
            return await asyncio.gather(
                _sim_collect(e, prompts[0], extras=extras),
                _sim_collect(e, prompts[1]))
        finally:
            e.stop()

    base = await run(False, None)
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.engine"):
        outs = await run(True, {"logprobs": 2})
    assert outs[1] == base[1]
    warns = [r for r in caplog.records
             if "incompatible with speculative" in r.getMessage()]
    assert len(warns) == 1, [r.getMessage() for r in caplog.records]


async def test_sim_spec_itl_per_token_and_accepted_per_step():
    """Satellites: a K+1-token emission must contribute per-token ITL
    samples (not one giant gap), and IterationRecord carries
    accepted_per_step for verify iterations."""
    r, e = _sim_engine(True, 0.9, recorder_size=256)
    e.start()
    try:
        req = {"token_ids": [4, 2] * 12,
               "sampling": {"temperature": 0.0, "seed": 3},
               "stop": {"max_tokens": 16, "stop_ids": []}}
        n_itl = None
        async for item in e.generate(req, Context()):
            if item["finish_reason"]:
                n_itl = len(item.get("phases", {}).get("itl_s", []) or [])
    finally:
        e.stop()
    assert e.spec_stats["verify_iters"] > 0
    # one ITL sample per generated token after the first
    assert n_itl == 15, n_itl
    recs = e.recorder.snapshot()
    spec_recs = [x for x in recs if x.accepted_per_step > 0]
    assert spec_recs, "no iteration recorded accepted_per_step"
    assert all(x.accepted_per_step <= e.spec_k + 1 for x in spec_recs)


# -- scheduler budgets ------------------------------------------------------


def _mk_seq(rid, n, max_tokens=64):
    return Sequence(request_id=rid, prompt=list(range(2, 2 + n)),
                    sampling={"temperature": 0.0},
                    stop={"max_tokens": max_tokens})


def _walk_to_running(sched, seq):
    from dynamo_tpu.engine.scheduler import (
        MixedPlan, PrefillPlan, SeqState)

    sched.add(seq)
    while seq.state != SeqState.RUNNING:
        plan = sched.step_plan()
        if isinstance(plan, MixedPlan):
            for i, d in enumerate(plan.decode.seqs):
                sched.complete_decode(d, 100 + i)
            for p in plan.prefills:
                sched.complete_prefill(p)
        else:
            assert isinstance(plan, PrefillPlan)
            sched.complete_prefill(plan)
    sched.complete_decode(seq, 10, advance_computed=False)
    return seq


def test_scheduler_trims_drafts_to_mixed_budget_and_seg_budget():
    pool = PagePool(num_pages=256, page_size=4)
    sched = Scheduler(pool, max_batch=8, chunk_size=16,
                      max_seq_pages=32, mixed_prefill_tokens=10,
                      decode_steps=4, spec_seg_budget=96)
    running = [_walk_to_running(sched, _mk_seq(f"r{i}", 8))
               for i in range(2)]
    # a late arrival goes through chunked prefill, eating the mixed pool
    sched.add(_mk_seq("late", 8))
    for s in running:
        s.spec_draft = list(range(20, 28))  # 8 drafted tokens each
    plan = sched.step_plan()
    # budget: 10 tokens - prefill chunk(s) first, leftover split by order
    chunk_tokens = sum(len(p.chunk) for p in plan.prefills)
    drafted = sum(len(s.spec_draft) for s in plan.decode.seqs)
    assert chunk_tokens > 0
    assert drafted <= 10 - chunk_tokens
    assert plan.decode.n_steps == 1  # spec forces single-step
    # budget exhausted in order: first seq drafts survive first
    assert len(plan.decode.seqs[0].spec_draft) >= len(
        plan.decode.seqs[1].spec_draft)


def test_scheduler_spec_max_tokens_cap_and_zero_budget():
    pool = PagePool(num_pages=256, page_size=4)
    sched = Scheduler(pool, max_batch=8, chunk_size=16, max_seq_pages=32,
                      mixed_prefill_tokens=64, spec_max_tokens=3)
    s = _walk_to_running(sched, _mk_seq("a", 6))
    s.spec_draft = [9, 9, 9, 9, 9]
    plan = sched.step_plan()
    assert len(plan.seqs[0].spec_draft) == 3  # absolute per-iter cap
    # mixed_prefill_tokens=0 (strict alternation) disables speculation
    sched2 = Scheduler(pool, max_batch=8, chunk_size=16, max_seq_pages=32,
                       mixed_prefill_tokens=0)
    s2 = _walk_to_running(sched2, _mk_seq("b", 6))
    s2.spec_draft = [9, 9, 9]
    plan2 = sched2.step_plan()
    assert plan2.seqs[0].spec_draft == []


def test_scheduler_draft_clipped_to_max_tokens_remaining():
    pool = PagePool(num_pages=256, page_size=4)
    sched = Scheduler(pool, max_batch=4, chunk_size=16, max_seq_pages=32,
                      mixed_prefill_tokens=64)
    s = _walk_to_running(sched, _mk_seq("a", 6, max_tokens=2))
    assert s.n_generated == 1
    s.spec_draft = [7, 7, 7, 7]
    plan = sched.step_plan()
    # only 1 more token may be generated -> at most 1 draft survives
    assert len(plan.seqs[0].spec_draft) <= 1


# -- SimTiming charge model -------------------------------------------------


def test_sim_timing_spec_charge_tokens():
    ragged = SimTiming(speed=0.0)
    padded = SimTiming(speed=0.0, prefill_cost="padded")
    # each speculating row bills drafted+1 flat tokens under ragged cost
    assert ragged.spec_charge_tokens([4, 0, 2]) == (4 + 1) + (2 + 1)
    assert ragged.spec_charge_tokens([]) == 0
    assert ragged.spec_charge_tokens([0, 0]) == 0
    # padded mode buckets the rows like chunks (strictly >= ragged)
    assert padded.spec_charge_tokens([4, 2]) >= ragged.spec_charge_tokens(
        [4, 2])


def test_sim_runner_verify_spec_bills_and_chains():
    """verify_spec rows must continue the EXACT chained token stream
    decode_multi produces (dispatch-boundary invariance), and bill
    drafted+1 tokens per row into the packed/spec counters."""
    r = SimRunner(num_pages=64, page_size=4, max_pages_per_seq=16,
                  timing=SimTiming(speed=0.0), spec_accept_rate=1.0)
    pt = [list(range(4))]
    # plain chained multi-step decode from token 5 at pos 10
    toks = np.asarray(r.decode_multi(3, [5], [10], pt, {"temperature": [0.0]}, 0))
    stream = [int(t) for t in toks[0]]
    # a perfect oracle draft replayed through verify_spec: row[j] must
    # reproduce the same stream (sampled at each fed position)
    draft = r.spec_draft(5, 10, 2)
    assert draft == stream[:2]
    rows, chunk_logits = r.verify_spec(
        [5], [10], pt, [draft], {"temperature": [0.0]}, 0)
    assert [int(t) for t in rows[0]] == stream[:3]
    assert chunk_logits == []
    assert r.stats["spec_dispatches"] == 1
    assert r.stats["spec_tokens_charged"] == 3  # K+1 with K=2


# -- real runner: T-bucket stability ---------------------------------------


async def test_real_runner_spec_byte_identity_and_zero_new_variants(
        monkeypatch):
    """Tentpole acceptance: n-gram speculation on the REAL runner rides
    the existing ragged program — greedy outputs byte-identical to plain
    decoding and ZERO new compile families/variants vs spec-off."""
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    prompts = [[4, 2] * 4, [9, 8, 7, 1] * 2, [1, 2, 3] * 3]

    def mk():
        return ModelRunner(get_config("tiny"), num_pages=96, page_size=4,
                           max_pages_per_seq=16, decode_buckets=(1, 2, 4),
                           prefill_buckets=(8, 16), seed=7)

    async def serve(runner, spec, concurrent):
        engine = InferenceEngine(runner, max_batch=6, chunk_size=8,
                                 mixed_prefill_tokens=16,
                                 mixed_prefill_seqs=4, mixed_min_chunk=2,
                                 spec_ngram=spec, spec_k=3)
        engine.start()
        try:
            async def one(p, i):
                toks = []
                async for item in engine.generate(
                    {"token_ids": p,
                     "sampling": {"temperature": 0.0, "seed": 11 + i},
                     "stop": {"max_tokens": 8, "stop_ids": []}}, Context(),
                ):
                    assert item.get("finish_reason") != "error", item
                    toks.extend(item["token_ids"])
                    if item["finish_reason"]:
                        break
                return toks
            if concurrent:
                outs = await asyncio.gather(
                    *[one(p, i) for i, p in enumerate(prompts)])
            else:
                outs = [await one(p, i) for i, p in enumerate(prompts)]
            return outs, engine.spec_stats
        finally:
            engine.stop()

    solo, _ = await serve(mk(), False, False)
    r_off = mk()
    await serve(r_off, False, True)
    fams_off = {k: v["variants"] for k, v in r_off.compile_stats().items()}
    r_on = mk()
    conc, st = await serve(r_on, True, True)
    assert st["verify_iters"] > 0 and st["accepted"] > 0, st
    assert _sha(solo) == _sha(conc), (solo, conc)
    fams_on = {k: v["variants"] for k, v in r_on.compile_stats().items()}
    assert set(fams_on) == set(fams_off), (fams_off, fams_on)
    assert fams_on["ragged"] == fams_off["ragged"], (fams_off, fams_on)


# -- tree speculation -------------------------------------------------------

from dynamo_tpu.engine.ngram_draft import accept_tree, propose_tree


def test_propose_tree_branch0_equals_propose_and_dedups():
    toks = [1, 2, 3, 9, 1, 2, 3, 7, 1, 2, 3]
    assert propose_tree(toks, 4, 1) == [propose(toks, 4)]
    tree = propose_tree(toks, 4, 3)
    assert tree[0] == propose(toks, 4)
    assert len({tuple(b) for b in tree}) == len(tree)  # deduped
    assert all(len(b) <= len(tree[0]) for b in tree[1:])  # clipped
    assert propose_tree([5], 4, 2) == []  # too short to match anything


def test_accept_tree_one_branch_equals_accept_deterministic():
    rng = np.random.default_rng(0)
    for _ in range(300):
        k = int(rng.integers(1, 5))
        draft = rng.integers(0, 9, k).tolist()
        row = rng.integers(0, 9, k + 1).tolist()
        out, winner = accept_tree([draft], [row])
        assert out == accept_deterministic(draft, row)
        assert winner == 0


def test_accept_tree_walks_trie_and_reports_winner():
    # branch 1 rescues a primary mismatch at depth 1 and carries the
    # walk to its own bonus token
    out, w = accept_tree([[5, 6, 7], [5, 8, 7]],
                         [[5, 8, 1, 0], [5, 8, 7, 3]])
    assert (out, w) == ([5, 8, 7, 3], 1)
    # mismatch everywhere at depth 0: the primary's sample corrects
    out, w = accept_tree([[4], [6]], [[9, 0], [9, 0]])
    assert (out, w) == ([9], 0)
    # primary full match beats a diverging sibling: bonus from row 0
    out, w = accept_tree([[5, 6], [5, 9]], [[5, 6, 42], [5, 9, 7]])
    assert (out, w) == ([5, 6, 42], 0)


def test_accept_tree_statistical_pin_preserves_target_distribution():
    """temp>0 losslessness for the TREE walk: verify rows of branches
    sharing a drafted prefix sample identically on that prefix (same
    params, same seed, same fed tokens — the property real verify rows
    have by construction). Model that with one lazy target sample per
    distinct prefix; the marginal of emitted[j] given the walk reached
    depth j must then equal the target law p for ANY draft tree."""
    rng = np.random.default_rng(7)
    V, N = 5, 20000
    p = np.asarray([0.4, 0.25, 0.15, 0.12, 0.08])
    drafts = [[0, 1, 2], [0, 0, 1], [1, 1, 1]]
    counts = np.zeros((4, V))
    reached = np.zeros(4)
    for _ in range(N):
        cache = {}

        def sample_for(prefix):
            if prefix not in cache:
                cache[prefix] = int(rng.choice(V, p=p))
            return cache[prefix]

        rows = [[sample_for(tuple(d[:j])) for j in range(len(d) + 1)]
                for d in drafts]
        out, _ = accept_tree(drafts, rows)
        for j, t in enumerate(out):
            counts[j, t] += 1
            reached[j] += 1
    for j in range(4):
        if reached[j] < 2000:
            continue
        emp = counts[j] / reached[j]
        assert np.abs(emp - p).max() < 0.03, (j, emp, reached[j])


def _tree_engine(spec=False, rate=None, k=4, branches=1, speed=0.0):
    runner = SimRunner(num_pages=512, page_size=4, max_pages_per_seq=64,
                       timing=SimTiming(speed=speed),
                       spec_accept_rate=rate)
    engine = InferenceEngine(
        runner, max_batch=8, chunk_size=16, decode_steps=4,
        mixed_prefill_tokens=64, spec_ngram=spec, spec_k=k,
        spec_branches=branches,
    )
    return runner, engine


async def test_sim_tree_greedy_byte_identity_and_switches():
    """Tree verify rows must not perturb greedy output: sha-identical to
    plain AND to linear-K speculation, across the oracle tree drafter
    (corrupted siblings) and the host n-gram tree — and at least one
    branch adoption must actually happen so the fork/adopt path is
    exercised, not just compiled."""
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6] * 4,
               [2, 7] * 10, [1, 2, 3, 4, 5] * 5]

    async def run(spec, rate, branches):
        _, e = _tree_engine(spec, rate, branches=branches)
        e.start()
        try:
            outs = await asyncio.gather(
                *[_sim_collect(e, p) for p in prompts])
            return outs, e.spec_stats
        finally:
            e.stop()

    base, _ = await run(False, None, 1)
    want = _sha(base)
    linear, st_lin = await run(True, 0.6, 1)
    assert _sha(linear) == want
    assert st_lin["tree_rows"] == 0 and st_lin["tree_switches"] == 0
    switched = 0
    for rate, branches in ((0.6, 2), (0.5, 3), (None, 3)):
        outs, st = await run(True, rate, branches)
        assert _sha(outs) == want, (rate, branches, base, outs)
        if rate is not None:
            assert st["tree_rows"] > 0, st  # branches actually dispatched
        switched += st["tree_switches"]
    assert switched > 0, "no branch adoption ever happened"


async def test_sim_tree_kv_pool_state_matches_plain():
    """Fork/adopt/release accounting: after identical traffic the pool
    must hold zero live refs and the same free-page count and prefix
    hash registry as plain decoding — losing branches, adopted trunks
    and aborted forks all balance out."""
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6] * 4, [2, 7] * 10]

    async def run(spec, branches):
        r, e = _tree_engine(spec, 0.7, branches=branches)
        e.start()
        try:
            await asyncio.gather(*[_sim_collect(e, p) for p in prompts])
            follow = await _sim_collect(e, prompts[0][:16] + [8, 8])
        finally:
            e.stop()
        pool = e.scheduler.pool
        assert not pool.ref, pool.ref  # no live refs after all finished
        return (sorted(pool.by_hash.keys()), pool.n_free), follow

    plain, follow_plain = await run(False, 1)
    tree, follow_tree = await run(True, 3)
    assert plain == tree
    assert follow_plain == follow_tree


async def test_sim_tree_abort_releases_forks():
    """A client that walks away mid-stream while tree verify rows are in
    flight must leak nothing: scheduler drains and the pool drops every
    ref (trunk AND branch forks)."""
    r, e = _tree_engine(True, 0.8, branches=3, speed=1.0)
    r.timing.decode_base_s = 0.02
    r.timing.dispatch_overhead_s = 0.0
    e.start()
    try:
        req = {"token_ids": [4, 2] * 12,
               "sampling": {"temperature": 0.0, "seed": 3},
               "stop": {"max_tokens": 64, "stop_ids": []}}
        got = 0
        gen = e.generate(req, Context())
        async for item in gen:
            got += len(item["token_ids"])
            if got >= 2:
                break
        await gen.aclose()
        for _ in range(100):
            if not e.scheduler.active and not e.pool.ref:
                break
            await asyncio.sleep(0.05)
        assert not e.scheduler.active
        assert not e.pool.ref, e.pool.ref
    finally:
        e.stop()


# -- device-resident draft ring --------------------------------------------


def test_sim_draft_ring_matches_host_propose():
    r = SimRunner(num_pages=64, page_size=4, max_pages_per_seq=16,
                  timing=SimTiming(speed=0.0))
    D = r.ensure_draft_ring(4, 3)
    assert D >= 3 + 2
    toks = [1, 2, 3, 9, 1, 2, 3, 7, 1, 2]
    r.draft_ring_reset(0, toks)
    r.draft_ring_reset(1, toks[:6])
    drafts, n_prop = r.draft_step([], 3)
    assert [int(t) for t in drafts[0][: n_prop[0]]] == propose(toks, 3)
    # appending the tail as a delta must land in the same state
    drafts, n_prop = r.draft_step([(1, toks[6:])], 3)
    assert [int(t) for t in drafts[1][: n_prop[1]]] == propose(toks, 3)
    assert r.stats["draft_dispatches"] == 2


async def test_sim_engine_device_draft_byte_identity():
    """With no oracle configured, the engine routes drafting through the
    runner's draft ring; greedy output must stay byte-identical to both
    plain decode and host n-gram drafting."""
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6] * 4, [2, 7] * 10]

    async def run(spec, device):
        r, e = _tree_engine(spec, None)
        if not device:
            e._spec_device_draft = False
        e.start()
        try:
            outs = await asyncio.gather(
                *[_sim_collect(e, p) for p in prompts])
            return outs, r.stats.get("draft_dispatches", 0)
        finally:
            e.stop()

    base, _ = await run(False, False)
    host, n_host = await run(True, False)
    dev, n_dev = await run(True, True)
    assert _sha(host) == _sha(base)
    assert _sha(dev) == _sha(base)
    assert n_host == 0 and n_dev > 0, (n_host, n_dev)


def test_real_runner_draft_ring_matches_host_propose():
    """The jitted gather ring must be bit-identical to ngram_draft.propose
    for histories within the ring window, across resets and chained
    delta appends."""
    runner = ModelRunner(get_config("tiny"), num_pages=16, page_size=4,
                         max_pages_per_seq=4, seed=0)
    D = runner.ensure_draft_ring(3, 4)
    rng = np.random.default_rng(5)
    hists = [rng.integers(16, 30, size=int(rng.integers(2, 60))).tolist()
             for _ in range(3)]
    for s, h in enumerate(hists):
        runner.draft_ring_reset(s, h)
    drafts, n_prop = runner.draft_step([], 4)
    for s, h in enumerate(hists):
        got = [int(t) for t in drafts[s][: int(n_prop[s])]]
        assert got == propose(h, 4), (s, h, got)
    for _ in range(5):
        upd = []
        for s in range(3):
            d = rng.integers(16, 30, size=int(rng.integers(0, D))).tolist()
            hists[s].extend(d)
            if d:
                upd.append((s, d))
        drafts, n_prop = runner.draft_step(upd, 4)
        for s, h in enumerate(hists):
            got = [int(t) for t in drafts[s][: int(n_prop[s])]]
            assert got == propose(h, 4), (s, h, got)
