"""Native JAX engine tests (CPU): paged forward correctness, KV pool
lifecycle + prefix cache, scheduler batching/preemption, and the async
engine end-to-end with the tiny model."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.kv_pool import NoSpace, PagePool
from dynamo_tpu.engine.scheduler import Scheduler, SeqState, Sequence
from dynamo_tpu.tokens.hashing import block_hashes, hash_block


# -- hashing ----------------------------------------------------------------


def test_block_hashes_lineage():
    a = block_hashes([1, 2, 3, 4, 5, 6, 7], 2)
    assert len(a) == 3  # 3 complete blocks of 2
    b = block_hashes([1, 2, 3, 4], 2)
    assert a[:2] == b  # shared prefix, same lineage hashes
    c = block_hashes([9, 2, 3, 4], 2)
    assert c[0] != b[0] and c[1] != b[1]  # different first block poisons chain
    assert hash_block(None, [1, 2]) == a[0]


# -- page pool --------------------------------------------------------------


def test_pool_alloc_release_roundtrip():
    pool = PagePool(8, 4)
    pages = pool.alloc(3)
    assert len(set(pages)) == 3 and pool.n_free == 5
    pool.release(pages)
    assert pool.n_free == 8


def test_pool_prefix_cache_and_eviction():
    pool = PagePool(4, 2)
    tokens = [1, 2, 3, 4]
    pages = pool.alloc(2)
    hs = block_hashes(tokens, 2)
    pool.register(pages[0], hs[0], None)
    pool.register(pages[1], hs[1], hs[0])
    pool.release(pages)  # refcount 0 → cached, not freed
    assert pool.n_free == 4  # evictable counts as free

    m_pages, m_hashes = pool.match_prefix([1, 2, 3, 4, 5, 6])
    assert m_pages == pages and m_hashes == hs
    events = pool.drain_events()
    assert [e.kind for e in events] == ["store", "store"]

    pool.release(m_pages)
    # force eviction by allocating everything
    all_pages = pool.alloc(4)
    ev = pool.drain_events()
    assert any(e.kind == "remove" for e in ev)
    assert pool.match_prefix([1, 2]) == ([], [])
    with pytest.raises(NoSpace):
        pool.alloc(1)
    pool.release(all_pages)


# -- scheduler --------------------------------------------------------------


def _seq(rid, prompt, max_tokens=8):
    return Sequence(
        request_id=rid, prompt=list(prompt), sampling={},
        stop={"max_tokens": max_tokens, "stop_ids": [999]},
    )


def test_scheduler_prefill_then_decode_cycle():
    pool = PagePool(16, 4)
    sch = Scheduler(pool, max_batch=4, chunk_size=4)
    sch.add(_seq("a", [1, 2, 3, 4, 5, 6]))

    plan = sch.step_plan()  # first prefill chunk
    assert plan.chunk == [1, 2, 3, 4] and not plan.is_last_chunk
    sch.complete_prefill(plan)
    plan = sch.step_plan()  # second chunk
    assert plan.chunk == [5, 6] and plan.is_last_chunk
    sch.complete_prefill(plan)
    seq = plan.seq
    assert seq.state == SeqState.RUNNING
    assert sch.complete_decode(seq, 10, advance_computed=False) is None  # prefill-sampled token

    plan = sch.step_plan()
    assert hasattr(plan, "seqs") and plan.seqs == [seq]
    # run until max_tokens (step_plan each iteration extends pages)
    reasons = []
    for t in range(20):
        plan = sch.step_plan()
        if plan is None:
            break
        r = sch.complete_decode(seq, 100 + t)
        reasons.append(r)
        if r:
            break
    assert reasons[-1] == "length" and seq.n_generated == 8
    assert pool.n_free == 16  # everything released (some pages cached)


def test_scheduler_mixed_coschedule():
    """With decode work present, an arriving prompt prefills in bounded
    chunks IN THE SAME iteration as the decode batch (MixedPlan) — decode
    never stalls behind prompt processing (VERDICT r4 #2 / the reference
    planner's chunked-prefill model)."""
    from dynamo_tpu.engine.scheduler import DecodePlan, MixedPlan, PrefillPlan

    pool = PagePool(32, 4)
    sch = Scheduler(pool, max_batch=4, chunk_size=64, mixed_prefill_tokens=4)
    a = _seq("a", [1, 2, 3], max_tokens=20)
    sch.add(a)
    plan = sch.step_plan()
    assert isinstance(plan, PrefillPlan)  # no decode work yet: full chunk
    sch.complete_prefill(plan)
    sch.complete_decode(a, 10, advance_computed=False)

    b = _seq("b", list(range(1, 13)), max_tokens=20)  # 12-token prompt
    sch.add(b)
    decode_iterations = 0
    while b.state != SeqState.RUNNING:  # admission happens inside step_plan
        plan = sch.step_plan()
        assert isinstance(plan, MixedPlan), plan
        assert plan.decode.seqs == [a] and len(plan.prefill.chunk) <= 4
        sch.complete_decode(a, 20 + decode_iterations)  # decode half ran
        sch.complete_prefill(plan.prefill)
        decode_iterations += 1
    # 12 tokens / 4-token mixed cap = 3 iterations, decode advanced in each
    assert decode_iterations == 3 and a.n_generated == 4
    sch.complete_decode(b, 50, advance_computed=False)
    plan = sch.step_plan()
    assert isinstance(plan, DecodePlan) and len(plan.seqs) == 2
    assert a in plan.seqs and b in plan.seqs


def test_scheduler_mixed_disabled_is_prefill_first():
    from dynamo_tpu.engine.scheduler import PrefillPlan

    pool = PagePool(32, 4)
    sch = Scheduler(pool, max_batch=4, chunk_size=4, mixed_prefill_tokens=0)
    a = _seq("a", [1, 2, 3], max_tokens=20)
    sch.add(a)
    sch.complete_prefill(sch.step_plan())
    sch.complete_decode(a, 10, advance_computed=False)
    sch.add(_seq("b", list(range(1, 10)), max_tokens=20))
    plan = sch.step_plan()  # legacy: prefill preempts the decode batch
    assert isinstance(plan, PrefillPlan) and plan.chunk == [1, 2, 3, 4]


def test_scheduler_stop_id_finishes():
    pool = PagePool(16, 4)
    sch = Scheduler(pool, max_batch=4, chunk_size=64)
    sch.add(_seq("a", [1, 2, 3]))
    plan = sch.step_plan()
    sch.complete_prefill(plan)
    assert sch.complete_decode(plan.seq, 999, advance_computed=False) == "stop"
    assert plan.seq.finish_reason == "stop"


def test_scheduler_prefix_cache_reuse_across_requests():
    pool = PagePool(32, 4)
    sch = Scheduler(pool, max_batch=4, chunk_size=64)
    prompt = list(range(1, 13))  # 12 tokens = 3 complete pages
    s1 = _seq("a", prompt, max_tokens=1)
    sch.add(s1)
    plan = sch.step_plan()
    sch.complete_prefill(plan)
    sch.complete_decode(s1, 50, advance_computed=False)  # finishes (max_tokens=1), pages cached

    s2 = _seq("b", prompt + [77], max_tokens=1)
    sch.add(s2)
    plan2 = sch.step_plan()
    # 3 complete pages of the 12-token prefix are shared; only the tail
    # (12th pos is in page 3) needs compute
    assert s2.n_shared_pages == 3
    assert s2.computed_len == 12
    assert plan2.chunk == [77]


def test_scheduler_preemption_recompute():
    pool = PagePool(6, 2)  # very tight: 12 token slots
    # strict alternation: this test drives prefill completion by hand
    sch = Scheduler(pool, max_batch=4, chunk_size=64,
                    enable_prefix_cache=False, mixed_prefill_tokens=0)
    a = _seq("a", [1, 2, 3], max_tokens=20)
    b = _seq("b", [4, 5, 6], max_tokens=20)
    sch.add(a)
    sch.add(b)
    # prefill both
    for _ in range(2):
        plan = sch.step_plan()
        sch.complete_prefill(plan)
        sch.complete_decode(plan.seq, 10, advance_computed=False)
    # decode until pool pressure forces preemption of the youngest (b)
    preempted = False
    for step in range(10):
        plan = sch.step_plan()
        if plan is None:
            break
        if b.state == SeqState.WAITING:
            preempted = True
            break
        for s in list(plan.seqs):
            sch.complete_decode(s, 20 + step)
    assert preempted and b.n_preemptions == 1
    # b's prompt now carries its generated tokens for recompute
    assert len(b.prompt) == len(b.tokens)


# -- engine e2e (tiny model, CPU) -------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine():
    from dynamo_tpu.engine.engine import InferenceEngine
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config

    runner = ModelRunner(
        get_config("tiny"),
        num_pages=64,
        page_size=4,
        max_pages_per_seq=16,
        decode_buckets=(1, 2, 4, 8),
        prefill_buckets=(8, 16, 32),
    )
    engine = InferenceEngine(runner, max_batch=8, chunk_size=16)
    engine.start()
    yield engine
    engine.stop()


def _req(prompt, max_tokens=8, temperature=0.0, seed=0):
    return {
        "token_ids": prompt,
        "sampling": {"temperature": temperature, "seed": seed},
        "stop": {"max_tokens": max_tokens, "stop_ids": []},
    }


async def _collect(engine, req):
    from dynamo_tpu.runtime.context import Context

    toks, finish = [], None
    async for item in engine.generate(req, Context()):
        toks.extend(item["token_ids"])
        if item["finish_reason"]:
            finish = item["finish_reason"]
    return toks, finish


async def test_engine_greedy_deterministic(tiny_engine):
    req = _req([5, 6, 7, 8, 9], max_tokens=6)
    t1, f1 = await _collect(tiny_engine, req)
    t2, f2 = await _collect(tiny_engine, req)
    assert t1 == t2 and len(t1) == 6
    assert f1 == f2 == "length"
    assert all(0 <= t < 512 for t in t1)


async def test_engine_concurrent_requests(tiny_engine):
    reqs = [_req([i + 1, i + 2, i + 3], max_tokens=5) for i in range(6)]
    results = await asyncio.gather(*[_collect(tiny_engine, r) for r in reqs])
    assert all(len(t) == 5 and f == "length" for t, f in results)
    # concurrent batched decode must equal solo runs (greedy)
    solo, _ = await _collect(tiny_engine, reqs[0])
    assert results[0][0] == solo


async def test_engine_prefix_cache_hit_consistency(tiny_engine):
    base = [11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21, 22]
    t1, _ = await _collect(tiny_engine, _req(base, max_tokens=4))
    # second request shares the cached prefix pages but must produce
    # identical greedy output
    t2, _ = await _collect(tiny_engine, _req(base, max_tokens=4))
    assert t1 == t2


async def test_engine_cancellation(tiny_engine):
    from dynamo_tpu.runtime.context import Context

    ctx = Context()
    got = []
    async for item in tiny_engine.generate(_req([1, 2, 3], max_tokens=500), ctx):
        got.extend(item["token_ids"])
        if len(got) >= 3:
            ctx.stop_generating()
            break
    await asyncio.sleep(0.3)  # let the abort drain
    assert not tiny_engine.scheduler.active or all(
        s.request_id != ctx.id for s in tiny_engine.scheduler.active
    )


async def test_engine_stale_layout_kv_import_recomputes(tiny_engine):
    """A disagg-decode request whose transferred KV carries a stale wire
    layout version (mixed-version cluster, ADVICE r2) must fall back to
    local prefill — same greedy output as a plain request, no error."""
    from dynamo_tpu.engine.model_runner import KV_WIRE_LAYOUT_VERSION

    prompt = [31, 32, 33, 34, 35, 36, 37, 38]
    want, wf = await _collect(tiny_engine, _req(prompt, max_tokens=5))

    stale = {
        "data": True,
        "k": b"\x00" * 64,  # bytes would be mis-sliced if adopted
        "v": b"\x00" * 64,
        "shape": [1, 1, 4, 2, 4],
        "dtype": "bfloat16",
        "n_pages": 1,
        "layout": KV_WIRE_LAYOUT_VERSION - 1,
    }
    req = _req(prompt, max_tokens=5)
    req["annotations"] = {"disagg": "decode"}
    req["kv_import"] = stale
    before = sum(
        m.scheduled_tokens for m in tiny_engine.fpm_history if m.kind == "prefill"
    )
    got, gf = await _collect(tiny_engine, req)
    assert (got, gf) == (want, wf)
    after = sum(
        m.scheduled_tokens for m in tiny_engine.fpm_history if m.kind == "prefill"
    )
    assert after > before, "fallback must prefill locally, not adopt stale KV"


async def test_fused_mixed_dispatch_matches_sequential(monkeypatch):
    """Concurrent requests drive MixedPlan through the FUSED single-
    dispatch path (runner.decode_multi_with_prefills); greedy outputs must
    be identical to each prompt served alone (scheduling must never
    change results), and the fused path must actually engage. (Fusion
    defaults off on cpu — forced on here.)"""
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

    prompts = [[4, 2, 4, 2, 7, 5], [9, 8, 7, 1], [1, 2, 3, 4, 5, 6, 7, 8, 9]]

    async def serve(runner, concurrent):
        engine = InferenceEngine(runner, max_batch=4, chunk_size=8,
                                 mixed_prefill_tokens=8)
        engine.start()
        fused_calls = 0
        orig = runner.mixed_dispatch  # (the engine runs the step's halves)

        def counting(*a, **k):
            nonlocal fused_calls
            fused_calls += 1
            return orig(*a, **k)

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
            return out, fused_calls
        finally:
            engine.stop()

    seq_out, _ = await serve(mk(), concurrent=False)
    conc_out, fused_calls = await serve(mk(), concurrent=True)
    assert seq_out == conc_out, (seq_out, conc_out)
    assert fused_calls > 0, "concurrent load never engaged the fused path"


def test_uncapped_generation_stops_at_model_context():
    """A request with no max_tokens must finish with reason=length at the
    MODEL's max_seq_len, not run on to the page-table capacity: positions
    past the rope table produce garbage logits silently."""
    from dynamo_tpu.engine.engine import InferenceEngine
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.runtime.context import Context

    cfg = get_config("tiny").with_(max_seq_len=32)
    runner = ModelRunner(cfg, num_pages=64, page_size=8, max_pages_per_seq=16)
    eng = InferenceEngine(runner)

    async def run():
        ctx = Context()
        toks = []
        finish = None
        async for item in eng.generate({"token_ids": [1, 2, 3]}, ctx):
            toks += item.get("token_ids") or []
            finish = item.get("finish_reason") or finish
        return toks, finish

    toks, finish = asyncio.run(run())
    # page capacity is 16*8=128 tokens; the model context (32) must bind
    assert len(toks) + 3 <= 32
    assert finish == "length"

    # a PROMPT past the model context must be rejected at admission, not
    # silently prefilled beyond the rope-valid range
    async def run_long():
        ctx = Context()
        async for item in eng.generate({"token_ids": list(range(100))}, ctx):
            return item

    item = asyncio.run(run_long())
    assert item["finish_reason"] == "error"
    assert "exceeds" in item["error"]
    eng.stop()


def test_ragged_tail_chains_on_device_tokens_without_readback():
    """A mixed iteration on the ragged path is two dispatches: the ragged
    step samples step 0, the decode loop runs steps 1..n-1. The loop must
    take the step-0 tokens as the DEVICE array the ragged step produced —
    nothing read back, nothing re-staged from the host between the two —
    and the one readback comes after both are enqueued."""
    import jax
    import numpy as np
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config

    runner = ModelRunner(get_config("tiny"), num_pages=64, page_size=4,
                         max_pages_per_seq=16, decode_buckets=(1, 2, 4),
                         prefill_buckets=(8, 16), seed=3)
    assert runner.ragged_mixed
    prompts = [[5, 6, 7, 8], [9, 1, 2, 3]]
    samp = {"temperature": [0.0, 0.0], "top_k": [0, 0],
            "top_p": [1.0, 1.0], "seeds": [11, 12]}
    pts, first = [], []
    for i, p in enumerate(prompts):
        pt = list(range(4 * i, 4 * i + 4))
        logits = runner.prefill(p, 0, pt, 0)
        pts.append(pt)
        first.append(int(np.argmax(np.asarray(logits))))
    positions = [len(p) for p in prompts]
    chunk = {"tokens": [3, 1, 4, 1, 5], "start": 0, "table": [8, 9],
             "prior": 0, "adapter": 0}

    events = []

    def spy(fam, name):
        fn = fam._fn

        def call(*a, **k):
            events.append((name, a))
            return fn(*a, **k)

        call._cache_size = fn._cache_size
        fam._fn = call

    spy(runner._jit_ragged, "ragged")
    spy(runner._jit_decode_loop, "decode_loop")
    readback = runner._readback
    runner._readback = lambda x: (events.append(("readback", ())), readback(x))[1]

    # an implicit device-to-host transfer anywhere in the call would raise
    with jax.transfer_guard_device_to_host("disallow"):
        out = runner.decode_multi_with_prefills(
            4, first, positions, pts, samp, 0, [chunk])
    toks, rows = out
    assert out.ragged and toks.shape == (2, 4) and len(rows) == 1
    assert [e[0] for e in events] == ["ragged", "decode_loop", "readback"]
    n_steps, _, _, tokens0 = events[1][1][:4]
    assert n_steps == 3
    assert isinstance(tokens0, jax.Array) and tokens0.shape == (2,)
    # the chained loop continues the stream a single fused loop gives
    want = runner.decode_multi(4, first, positions, pts, samp, 0)[:2]
    assert (toks == want).all(), (toks, want)
