"""Ragged flat-token mixed dispatch (fast tier-1 suite).

Covers the runner's _prep_ragged/_jit_ragged path: byte identity against
the legacy [N, S] bucket-padded fused program on identical mixed plans,
compile-cardinality (one ragged variant across differently-shaped packs),
BucketOverflowError degradation (runner falls back to padded, engine
defers shed chunks instead of erroring the plan), and the mocker's
padded-vs-ragged packed-prefill cost accounting (ISSUE 3 acceptance).
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.model_runner import (
    BucketOverflowError,
    ModelRunner,
    _next_bucket,
)
from dynamo_tpu.models.config import get_config


# -- _next_bucket degradation (satellite: no bare ValueError) ---------------


def test_next_bucket_overflow_error():
    assert _next_bucket((1, 2, 4), 3) == 4
    with pytest.raises(BucketOverflowError) as ei:
        _next_bucket((1, 2, 4), 5)
    assert isinstance(ei.value, ValueError)  # old except-clauses still match
    assert ei.value.n == 5
    assert ei.value.largest == 4


# -- runner-level byte identity ---------------------------------------------


def _mk_runner(monkeypatch, ragged):
    r = ModelRunner(
        get_config("tiny"), num_pages=96, page_size=4,
        max_pages_per_seq=16, decode_buckets=(1, 2, 4),
        prefill_buckets=(8, 16), seed=7,
    )
    if not ragged:
        r.ragged_mixed = False  # the padded [N, S] program
    return r


def _run_mixed_plan(r):
    """One prefill round, then a packed mixed iteration (2 decode rows +
    2 chunks) and a singular mixed iteration — all pages disjoint, the
    invariant the scheduler guarantees within a plan."""
    pts = [list(range(i * 4, (i + 1) * 4)) for i in range(4)]
    prompts = [[4, 2, 4, 2, 7, 5], [9, 8, 7, 1]]
    feed = [int(np.argmax(np.asarray(r.prefill(p, 0, pts[i], 0))))
            for i, p in enumerate(prompts)]
    sampling = {"temperature": [0.0, 0.0], "top_k": [0, 0],
                "top_p": [1.0, 1.0], "seeds": [11, 22]}
    chunks = [
        {"tokens": [1, 2, 3, 4, 5, 6, 7], "start": 0, "table": pts[2],
         "prior": 0, "adapter": 0},
        {"tokens": [3, 1, 4], "start": 0, "table": pts[3],
         "prior": 0, "adapter": 0},
    ]
    toks, chunk_logits = r.decode_multi_with_prefills(
        3, feed, [len(p) for p in prompts], pts[:2], sampling, 0, chunks,
    )
    toks = np.asarray(toks)[:2]
    toks2, lg2 = r.decode_multi_with_prefill(
        2, [int(toks[0, -1]), int(toks[1, -1])],
        [len(prompts[0]) + 3, len(prompts[1]) + 3], pts[:2], sampling, 3,
        [5, 6, 7, 8], 3, pts[3], 3,
    )
    return (toks, np.asarray(chunk_logits)[:2],
            np.asarray(toks2)[:2], np.asarray(lg2))


def test_runner_ragged_byte_identity(monkeypatch):
    """Acceptance: the ragged flat-token path is byte-identical to the
    legacy padded path on the same mixed plan, and differently-shaped
    packs share ONE ragged compiled variant (the T bucket is the only
    compile key)."""
    legacy = _run_mixed_plan(_mk_runner(monkeypatch, ragged=False))
    r = _mk_runner(monkeypatch, ragged=True)
    ragged = _run_mixed_plan(r)
    for a, b in zip(legacy, ragged):
        assert np.array_equal(a, b), (a, b)
    stats = r.compile_stats()
    assert stats["ragged"]["variants"] == 1, stats
    assert stats["mixed"]["calls"] == 0, stats  # padded program never ran


def test_runner_ragged_t_bucket_overflow_falls_back(monkeypatch):
    """T-bucket-overflow edge: a plan larger than every ragged bucket
    must not fail — the runner degrades to the legacy padded program and
    the outputs stay byte-identical."""
    legacy = _run_mixed_plan(_mk_runner(monkeypatch, ragged=False))
    r = _mk_runner(monkeypatch, ragged=True)
    r.ragged_buckets = (8,)  # 2 decode rows + 10 chunk tokens won't fit
    out = _run_mixed_plan(r)
    for a, b in zip(legacy, out):
        assert np.array_equal(a, b), (a, b)
    stats = r.compile_stats()
    # degradation is per plan: the 12-token packed plan fell back to the
    # padded program, the 6-token singular plan still rode ragged
    assert stats["mixed"]["calls"] > 0, stats
    assert stats["ragged"]["calls"] > 0, stats


# -- engine-level byte identity + overflow deferral -------------------------


_PROMPTS = [
    [4, 2, 4, 2, 7, 5],
    [9, 8, 7, 1],
    [1, 2, 3, 4, 5, 6, 7, 8, 9],
    [3, 1, 4, 1, 5],
]


async def _serve(runner, concurrent, hook=None):
    from dynamo_tpu.engine.engine import InferenceEngine
    from dynamo_tpu.runtime.context import Context

    engine = InferenceEngine(runner, max_batch=6, chunk_size=8,
                             mixed_prefill_tokens=8,
                             mixed_prefill_seqs=4, mixed_min_chunk=2)
    if hook is not None:
        hook(engine)
    engine.start()
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
            return await asyncio.gather(*[one(p) for p in _PROMPTS])
        return [await one(p) for p in _PROMPTS]
    finally:
        engine.stop()


async def test_engine_ragged_dispatch_byte_identity(monkeypatch):
    """Concurrent serving through the ragged mixed dispatch == each prompt
    served alone, and the ragged program actually engages under load."""
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    solo = await _serve(_mk_runner(monkeypatch, ragged=False),
                        concurrent=False)
    r = _mk_runner(monkeypatch, ragged=True)
    ragged_calls = 0
    orig = r._decode_multi_with_prefills_ragged

    def counting(*a, **k):
        nonlocal ragged_calls
        ragged_calls += 1
        return orig(*a, **k)

    r._decode_multi_with_prefills_ragged = counting
    conc = await _serve(r, concurrent=True)
    assert solo == conc, (solo, conc)
    assert ragged_calls > 0, "burst never engaged the ragged program"


@pytest.mark.parametrize("ragged", [True, False])
def test_iteration_record_says_which_mixed_program_ran(monkeypatch, ragged):
    """The flight record's `ragged` flag comes from the runner's return:
    a single-chunk mixed iteration records ragged=True on a ragged runner
    (it read False until PR 30: the engine guessed from the chunk count)
    and False on a padded one."""
    import time

    from dynamo_tpu.engine.engine import InferenceEngine
    from dynamo_tpu.engine.scheduler import Sequence

    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    engine = InferenceEngine(_mk_runner(monkeypatch, ragged), max_batch=4,
                             chunk_size=8, mixed_prefill_tokens=8)

    def add(rid, prompt):
        engine._inbox.put(("add", Sequence(
            request_id=rid, prompt=prompt, sampling={"temperature": 0.0},
            stop={"max_tokens": 16, "stop_ids": []},
            arrival=time.monotonic())))

    add("a", _PROMPTS[0])
    engine._loop_once()  # a's prefill
    engine._loop_once()  # a decodes alone
    add("b", _PROMPTS[1])
    engine._loop_once()  # a's decode + b's only chunk, one dispatch
    engine._deliver()  # (its record waits for the next enqueue, or this)
    engine._flush_late_record()
    rec = engine.recorder.snapshot()[-1]
    assert (rec.kind, rec.fused, rec.n_chunks, rec.decode_seqs) == (
        "mixed", True, 1, 1), rec
    assert rec.ragged is ragged


async def test_engine_pack_overflow_defers_chunks(monkeypatch):
    """Regression (satellite 1): a pack past the largest pack bucket used
    to raise a bare ValueError mid-iteration and error every sequence in
    the plan. The engine must now shed overflow chunks to the next
    iteration and still produce byte-identical outputs."""
    monkeypatch.setenv("DYN_FUSED_MIXED", "1")
    solo = await _serve(_mk_runner(monkeypatch, ragged=False),
                        concurrent=False)
    r = _mk_runner(monkeypatch, ragged=False)
    r.pack_buckets = (1, 2)  # 3+ chunk packs overflow -> shed + defer
    conc = await _serve(r, concurrent=True)
    assert solo == conc, (solo, conc)


# -- mocker padded-cost mode (satellite 2) ----------------------------------


def test_sim_timing_padded_vs_ragged_charge():
    from dynamo_tpu.mocker.sim import SimTiming

    ragged = SimTiming(speed=0.0)
    padded = SimTiming(speed=0.0, prefill_cost="padded")
    lens = [512, 32, 32, 32]
    assert ragged.packed_charge_tokens(lens) == sum(lens)  # 608
    # padded: pack bucket for 4 chunks x chunk bucket for 512 tokens
    assert padded.packed_charge_tokens(lens) == 4 * 512
    with pytest.raises(ValueError):
        SimTiming(speed=0.0, prefill_cost="bogus").packed_charge_tokens([1])


def test_sim_runner_packed_token_accounting():
    """Acceptance: under the default (ragged) cost model the mocker bills
    a mixed-size pack exactly sum(chunk_tokens); under the padded model
    it bills the [N_bucket, S_bucket] rectangle the legacy device path
    really dispatched."""
    from dynamo_tpu.mocker.sim import SimRunner, SimTiming

    chunks = [
        {"tokens": list(range(300, 300 + n)), "start": 0,
         "table": [0], "prior": 0}
        for n in (512, 32, 32, 32)
    ]
    r = SimRunner(timing=SimTiming(speed=0.0))
    out = r.prefill_packed(chunks)
    assert len(out) == 4
    assert r.stats["packed_tokens_real"] == 608
    assert r.stats["packed_tokens_charged"] == 608

    rp = SimRunner(timing=SimTiming(speed=0.0, prefill_cost="padded"))
    out_p = rp.prefill_packed(chunks)
    assert out_p == out  # cost mode must never change tokens
    assert rp.stats["packed_tokens_real"] == 608
    assert rp.stats["packed_tokens_charged"] == 2048
