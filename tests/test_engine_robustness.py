"""Engine step-thread survivability: one poisoned step must fail ITS
request with finish_reason="error" and leave the loop serving later
requests. (A dead step thread strands every queued stream with no error
and no end — the failure mode surfaces as a distributed hang, which is
how the cross-worker KVBM layout bug originally presented.)"""

import asyncio

import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.models.config import get_config
from dynamo_tpu.runtime.context import Context


@pytest.fixture(scope="module")
def engine():
    runner = ModelRunner(
        get_config("tiny"),
        num_pages=16,
        page_size=4,
        max_pages_per_seq=8,
        decode_buckets=(1, 2),
        prefill_buckets=(8, 16),
        seed=3,
    )
    eng = InferenceEngine(runner, max_batch=2, chunk_size=16)
    eng.start()
    yield eng
    eng.stop()


async def _generate(engine, prompt, n=2):
    items = []
    req = {
        "token_ids": prompt,
        "sampling": {"temperature": 0.0},
        "stop": {"max_tokens": n, "stop_ids": []},
    }
    async for item in engine.generate(req, Context()):
        items.append(item)
        if item["finish_reason"]:
            break
    return items


async def test_poisoned_step_errors_request_and_loop_survives(engine):
    # sanity: the engine works
    ok = await _generate(engine, [1, 2, 3])
    assert ok[-1]["finish_reason"] == "stop" or ok[-1]["finish_reason"] == "length"

    # poison exactly one prefill dispatch
    orig = engine.runner.prefill
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        raise RuntimeError("injected step failure")

    engine.runner.prefill = boom
    try:
        items = await asyncio.wait_for(_generate(engine, [4, 5, 6]), timeout=30)
    finally:
        engine.runner.prefill = orig
    assert calls["n"] == 1
    assert items[-1]["finish_reason"] == "error"

    # the loop survived: later requests still complete normally
    again = await asyncio.wait_for(_generate(engine, [7, 8, 9]), timeout=30)
    assert again[-1]["finish_reason"] in ("stop", "length")


async def test_donated_pool_poisoning_recovers(engine):
    """A step that consumes the donated pools and THEN fails must not
    leave the worker in a permanent 'Array has been deleted' error loop:
    the engine rebuilds zeroed pools, wipes page bookkeeping, and serves
    subsequent requests."""
    import jax

    orig = engine.runner.decode_dispatch

    def consume_and_fail(*a, **kw):
        # mimic a jit failure after donation: buffers gone, call raised
        for arr in jax.tree.leaves((engine.runner.k_pool, engine.runner.v_pool)):
            arr.delete()
        raise RuntimeError("injected post-donation failure")

    engine.runner.decode_dispatch = consume_and_fail
    try:
        items = await asyncio.wait_for(_generate(engine, [11, 12, 13]), timeout=30)
    finally:
        engine.runner.decode_dispatch = orig
    assert items[-1]["finish_reason"] == "error"
    # the error stream item is emitted before the step thread rebuilds the
    # pools — poll briefly rather than racing it
    for _ in range(100):
        if not engine.runner.pools_deleted():
            break
        await asyncio.sleep(0.1)
    assert not engine.runner.pools_deleted()

    ok = await asyncio.wait_for(_generate(engine, [14, 15, 16]), timeout=30)
    assert ok[-1]["finish_reason"] in ("stop", "length")
