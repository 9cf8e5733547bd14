"""Token-budget packed mixed scheduling (fast tier-1 suite).

Covers the packed MixedPlan plan shape (fair-share splitting, min-chunk
floor, single-chunk compatibility knob), its interactions with the prefix
cache / preemption / fused decode_steps, the packed ragged fused dispatch
byte-identity against solo serving, and a bursty-arrival mocker A/B
asserting the TTFT win that motivates packing (ISSUE 1 acceptance).
"""

import asyncio
import time

import pytest

from dynamo_tpu.engine.kv_pool import PagePool
from dynamo_tpu.engine.scheduler import (
    DecodePlan,
    MixedPlan,
    PrefillPlan,
    Scheduler,
    SeqState,
    Sequence,
)


def _seq(rid, prompt, max_tokens=8):
    return Sequence(
        request_id=rid, prompt=list(prompt), sampling={},
        stop={"max_tokens": max_tokens, "stop_ids": [999]},
    )


def _start_decode(sch, rid="dec", prompt=(1, 2, 3)):
    """Admit one sequence and walk it to RUNNING so step_plan co-schedules."""
    s = _seq(rid, list(prompt), max_tokens=64)
    sch.add(s)
    while s.state != SeqState.RUNNING:
        plan = sch.step_plan()
        if isinstance(plan, MixedPlan):
            for i, d in enumerate(plan.decode.seqs):
                sch.complete_decode(d, 100 + i)
            for p in plan.prefills:
                sch.complete_prefill(p)
        else:
            assert isinstance(plan, PrefillPlan)
            sch.complete_prefill(plan)
    sch.complete_decode(s, 10, advance_computed=False)
    return s


# -- plan shape -------------------------------------------------------------


def test_packed_plan_fair_share_oldest_first():
    """The budget splits across PREFILL sequences oldest-first; leftover
    share from a short prompt flows to the sequences behind it."""
    pool = PagePool(128, 4)
    sch = Scheduler(pool, max_batch=8, chunk_size=64,
                    mixed_prefill_tokens=32, mixed_prefill_seqs=4,
                    mixed_min_chunk=4)
    dec = _start_decode(sch)
    a = _seq("a", list(range(1, 41)), max_tokens=4)   # long: 40 tokens
    b = _seq("b", list(range(1, 7)), max_tokens=4)    # short: 6 tokens
    c = _seq("c", list(range(1, 41)), max_tokens=4)   # long: 40 tokens
    for s in (a, b, c):
        sch.add(s)
    plan = sch.step_plan()
    assert isinstance(plan, MixedPlan) and plan.decode.seqs == [dec]
    chunks = {p.seq.request_id: len(p.chunk) for p in plan.prefills}
    # oldest-first: a first, equal share 32//3=10; b takes only its 6;
    # c inherits the slack: (32-10-6)//1 = 16
    assert [p.seq.request_id for p in plan.prefills] == ["a", "b", "c"]
    assert chunks == {"a": 10, "b": 6, "c": 16}
    assert sum(chunks.values()) == 32  # pool fully used, never exceeded


def test_packed_plan_min_chunk_floor_and_seq_cap():
    """With many candidates the per-seq minimum binds (oldest sequences
    get real progress; the tail waits) and mixed_prefill_seqs caps the
    packed set."""
    pool = PagePool(256, 4)
    sch = Scheduler(pool, max_batch=12, chunk_size=64,
                    mixed_prefill_tokens=24, mixed_prefill_seqs=8,
                    mixed_min_chunk=8)
    _start_decode(sch)
    for i in range(6):
        sch.add(_seq(f"p{i}", list(range(1, 33)), max_tokens=4))
    plan = sch.step_plan()
    assert isinstance(plan, MixedPlan)
    # 24-token pool / 8-token floor → exactly the 3 oldest get chunks
    assert [p.seq.request_id for p in plan.prefills] == ["p0", "p1", "p2"]
    assert all(len(p.chunk) == 8 for p in plan.prefills)

    sch2 = Scheduler(PagePool(256, 4), max_batch=12, chunk_size=64,
                     mixed_prefill_tokens=64, mixed_prefill_seqs=2,
                     mixed_min_chunk=4)
    _start_decode(sch2)
    for i in range(4):
        sch2.add(_seq(f"q{i}", list(range(1, 33)), max_tokens=4))
    plan2 = sch2.step_plan()
    assert isinstance(plan2, MixedPlan)
    assert len(plan2.prefills) == 2  # seq cap binds before the budget


def test_single_chunk_knob_matches_legacy_plan():
    """mixed_prefill_seqs=1 reproduces the single-chunk MixedPlan: one
    chunk, full budget, oldest sequence — the A/B control arm."""
    pool = PagePool(128, 4)
    sch = Scheduler(pool, max_batch=8, chunk_size=64,
                    mixed_prefill_tokens=16, mixed_prefill_seqs=1)
    _start_decode(sch)
    sch.add(_seq("a", list(range(1, 41)), max_tokens=4))
    sch.add(_seq("b", list(range(1, 41)), max_tokens=4))
    plan = sch.step_plan()
    assert isinstance(plan, MixedPlan)
    assert len(plan.prefills) == 1 and plan.prefill.seq.request_id == "a"
    assert len(plan.prefill.chunk) == 16  # whole pool to the single chunk


def test_packed_progresses_all_sequences_to_running():
    """Driving packed plans to completion walks every prompt through
    PREFILL → RUNNING with per-chunk completion bookkeeping intact."""
    pool = PagePool(128, 4)
    sch = Scheduler(pool, max_batch=8, chunk_size=64,
                    mixed_prefill_tokens=16, mixed_prefill_seqs=4,
                    mixed_min_chunk=4)
    dec = _start_decode(sch)
    seqs = [_seq(f"s{i}", list(range(1, 13)), max_tokens=4) for i in range(3)]
    for s in seqs:
        sch.add(s)
    for _ in range(20):
        if all(s.state == SeqState.RUNNING for s in seqs):
            break
        plan = sch.step_plan()
        assert isinstance(plan, MixedPlan)
        for i, d in enumerate(plan.decode.seqs):
            sch.complete_decode(d, 100 + i)
        for p in plan.prefills:
            sch.complete_prefill(p)
    assert all(s.state == SeqState.RUNNING for s in seqs)
    # 3 prompts x 12 tokens at 16/iteration → all prefilled in 3 iterations
    assert dec.n_generated <= 1 + 3


# -- interactions -----------------------------------------------------------


def test_packed_prefill_with_prefix_cache_hit():
    """A packed candidate whose prefix is cached prefills only its tail;
    the budget it no longer needs goes to its packed siblings."""
    pool = PagePool(128, 4)
    sch = Scheduler(pool, max_batch=8, chunk_size=64,
                    mixed_prefill_tokens=32, mixed_prefill_seqs=4,
                    mixed_min_chunk=4)
    # seed the prefix cache: run a 16-token prompt to RUNNING (complete
    # pages register on prefill completion), then finish it
    warm = _seq("warm", list(range(1, 17)), max_tokens=1)
    sch.add(warm)
    while warm.state != SeqState.RUNNING:
        sch.complete_prefill(sch.step_plan())
    assert sch.complete_decode(warm, 999, advance_computed=False) == "stop"

    _start_decode(sch)
    hit = _seq("hit", list(range(1, 17)) + [77, 78], max_tokens=4)
    miss = _seq("miss", list(range(51, 91)), max_tokens=4)
    sch.add(hit)
    sch.add(miss)
    plan = sch.step_plan()
    assert isinstance(plan, MixedPlan)
    chunks = {p.seq.request_id: p for p in plan.prefills}
    # all 4 pages (16 tokens) of "hit"'s prefix came from cache — only
    # the tail beyond computed_len is scheduled
    assert hit.n_shared_pages == 4 and hit.computed_len == 16
    assert chunks["hit"].start_pos == 16
    assert len(chunks["hit"].chunk) == 2  # 18-token prompt - 16 cached
    assert chunks["hit"].is_last_chunk
    # sibling gets the fair share of the remainder
    assert len(chunks["miss"].chunk) > 0
    assert sum(len(p.chunk) for p in plan.prefills) <= 32


def test_packed_prefill_preemption_requeue():
    """Pool pressure during packed prefill: decode capacity preempts the
    youngest RUNNING sequence; the preempted sequence re-enters WAITING
    and later re-prefills (recompute) while packing continues."""
    pool = PagePool(20, 4)  # deliberately tight
    sch = Scheduler(pool, max_batch=8, chunk_size=64,
                    mixed_prefill_tokens=8, mixed_prefill_seqs=4,
                    mixed_min_chunk=4)
    a = _start_decode(sch, "a", prompt=list(range(1, 9)))
    b = _start_decode(sch, "b", prompt=list(range(11, 19)))
    c = _seq("c", list(range(21, 37)), max_tokens=4)
    sch.add(c)
    preempted = False
    c_ran = False
    for _ in range(60):
        plan = sch.step_plan()
        if plan is None:
            break
        if isinstance(plan, MixedPlan):
            for i, d in enumerate(plan.decode.seqs):
                sch.complete_decode(d, 100 + i)
            for p in plan.prefills:
                sch.complete_prefill(p)
        elif isinstance(plan, PrefillPlan):
            sch.complete_prefill(plan)
        else:
            for i, d in enumerate(plan.seqs):
                sch.complete_decode(d, 100 + i)
        preempted = preempted or any(
            s.n_preemptions > 0 for s in (a, b, c)
        )
        c_ran = c_ran or c.state in (SeqState.RUNNING, SeqState.FINISHED)
        if preempted and c_ran:
            break
    assert preempted, "tight pool never forced a preemption"
    assert c_ran  # packing survived the preemption/requeue churn


def test_packed_plan_respects_decode_steps_fusion():
    """Packing must not degrade multi-step decode fusion: the MixedPlan
    keeps decode_steps fused iterations alongside the packed chunk set."""
    pool = PagePool(128, 4)
    sch = Scheduler(pool, max_batch=8, chunk_size=64, decode_steps=4,
                    mixed_prefill_tokens=16, mixed_prefill_seqs=4,
                    mixed_min_chunk=4)
    _start_decode(sch)
    sch.add(_seq("a", list(range(1, 33)), max_tokens=8))
    sch.add(_seq("b", list(range(1, 33)), max_tokens=8))
    plan = sch.step_plan()
    assert isinstance(plan, MixedPlan)
    assert plan.decode.n_steps == 4
    assert len(plan.prefills) == 2
    # stats feed counts decode steps AND every packed prefill token
    assert sch.stats.scheduled_tokens == 1 * 4 + 16


# -- fused ragged dispatch (real tiny model) --------------------------------


async def test_packed_fused_dispatch_byte_identity(monkeypatch):
    """Acceptance: the packed ragged prefill + decode single-dispatch
    path produces greedy outputs identical to each prompt served alone
    (and to the sequential single-chunk machinery underneath), and the
    packed program (decode_multi_with_prefills, N>1) actually engages."""
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    from dynamo_tpu.engine.engine import InferenceEngine
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.runtime.context import Context

    def mk():
        return ModelRunner(
            get_config("tiny"), num_pages=96, page_size=4,
            max_pages_per_seq=16, decode_buckets=(1, 2, 4),
            prefill_buckets=(8, 16), seed=7,
        )

    prompts = [
        [4, 2, 4, 2, 7, 5],
        [9, 8, 7, 1],
        [1, 2, 3, 4, 5, 6, 7, 8, 9],
        [3, 1, 4, 1, 5],
    ]

    async def serve(runner, concurrent):
        engine = InferenceEngine(runner, max_batch=6, chunk_size=8,
                                 mixed_prefill_tokens=8,
                                 mixed_prefill_seqs=4, mixed_min_chunk=2)
        engine.start()
        packed_calls = 0
        orig = runner.mixed_dispatch  # (the engine runs the step's halves)

        def counting(n_steps, *a, **k):
            nonlocal packed_calls
            packed_calls += 1
            return orig(n_steps, *a, **k)

        runner.mixed_dispatch = counting
        try:
            async def one(p):
                toks = []
                async for item in engine.generate(
                    {"token_ids": p, "sampling": {"temperature": 0.0},
                     "stop": {"max_tokens": 6, "stop_ids": []}}, Context(),
                ):
                    assert item.get("finish_reason") != "error", item
                    toks.extend(item["token_ids"])
                    if item["finish_reason"]:
                        break
                return toks

            if concurrent:
                out = await asyncio.gather(*[one(p) for p in prompts])
            else:
                out = [await one(p) for p in prompts]
            return out, packed_calls
        finally:
            engine.stop()

    solo_out, _ = await serve(mk(), concurrent=False)
    conc_out, packed_calls = await serve(mk(), concurrent=True)
    assert solo_out == conc_out, (solo_out, conc_out)
    assert packed_calls > 0, "burst never engaged the packed fused program"


# -- bursty-arrival A/B (mocker) --------------------------------------------


def _mocker_engine(mixed_prefill_seqs, timing):
    from dynamo_tpu.engine.engine import InferenceEngine
    from dynamo_tpu.mocker.sim import SimRunner

    runner = SimRunner(num_pages=512, page_size=16, max_pages_per_seq=32,
                       timing=timing)
    return InferenceEngine(
        runner, max_batch=16, chunk_size=512, decode_steps=4,
        mixed_prefill_tokens=128, mixed_prefill_seqs=mixed_prefill_seqs,
        mixed_min_chunk=16,
    )


async def _burst(engine, n, isl, osl):
    """Fire n simultaneous arrivals; return (ttfts, itls) in seconds."""
    from dynamo_tpu.runtime.context import Context

    engine.start()
    try:
        async def one(i):
            start = time.monotonic()
            first = None
            stamps = []
            async for item in engine.generate(
                {"token_ids": [300 + i] * isl,
                 "sampling": {"temperature": 0.0},
                 "stop": {"max_tokens": osl, "stop_ids": [],
                          "ignore_eos": True}}, Context(),
            ):
                assert item.get("finish_reason") != "error", item
                now = time.monotonic()
                for _ in item.get("token_ids") or []:
                    stamps.append(now)
                if first is None and stamps:
                    first = now - start
                if item.get("finish_reason"):
                    break
            itls = [b - a for a, b in zip(stamps, stamps[1:])]
            return first, itls

        out = await asyncio.gather(*[one(i) for i in range(n)])
    finally:
        engine.stop()
    ttfts = sorted(x[0] for x in out)
    itls = sorted(v for x in out for v in x[1])
    return ttfts, itls


def _p99(vals):
    return vals[min(len(vals) - 1, int(0.99 * len(vals)))]


def test_bursty_arrival_packed_vs_single_chunk_ab():
    """8 simultaneous arrivals: token-budget packing must cut TTFT p99
    vs the single-chunk control while ITL p99 stays within 1.5x of the
    decode-only floor (ISSUE 1 acceptance; docs/perf_notes.md records
    the full-stack numbers)."""
    from dynamo_tpu.mocker.sim import SimTiming

    timing = SimTiming(prefill_base_s=0.002, prefill_per_token_s=0.00002,
                       decode_base_s=0.004, decode_per_seq_s=0.0003,
                       dispatch_overhead_s=0.002)
    single_ttft, _ = asyncio.run(
        _burst(_mocker_engine(1, timing), n=8, isl=96, osl=24))
    packed_ttft, packed_itl = asyncio.run(
        _burst(_mocker_engine(8, timing), n=8, isl=96, osl=24))
    # decode-only floor: same engine, negligible prefill work
    _, floor_itl = asyncio.run(
        _burst(_mocker_engine(8, timing), n=8, isl=8, osl=24))

    assert _p99(packed_ttft) < 0.9 * _p99(single_ttft), (
        packed_ttft, single_ttft
    )
    assert _p99(packed_itl) < 1.5 * _p99(floor_itl), (
        _p99(packed_itl), _p99(floor_itl)
    )


def test_mocker_packed_prefill_timing_model():
    """SimRunner.prefill_packed charges ONE dispatch base for the whole
    set plus per-token cost — and returns per-chunk logits that sample
    identically to per-chunk prefill (packing must not change tokens)."""
    from dynamo_tpu.mocker.sim import SimRunner, SimTiming

    r = SimRunner(timing=SimTiming(speed=0.0))
    chunks = [
        {"tokens": [5, 6, 7], "start": 0, "table": [0], "prior": 0},
        {"tokens": [8, 9], "start": 4, "table": [1], "prior": 4},
    ]
    packed = r.prefill_packed(chunks)
    solo = [
        r.prefill(c["tokens"], c["start"], c["table"], c["prior"])
        for c in chunks
    ]
    samp = {"temperature": [0.0], "top_k": [0], "top_p": [1.0], "seeds": [0]}
    assert [r.sample_one(lg, samp, 1) for lg in packed] == [
        r.sample_one(lg, samp, 1) for lg in solo
    ]

    slept = []
    r.timing.sleep = lambda s: slept.append(s)  # type: ignore[assignment]
    r.timing.speed = 1.0
    r.prefill_packed(chunks)
    for c in chunks:
        r.prefill(c["tokens"], c["start"], c["table"], c["prior"])
    t = r.timing
    assert slept[0] == pytest.approx(t.prefill_base_s + 5 * t.prefill_per_token_s)
    assert sum(slept[1:]) == pytest.approx(
        2 * t.prefill_base_s + 5 * t.prefill_per_token_s
    )
