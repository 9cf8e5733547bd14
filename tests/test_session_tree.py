"""Agentic session-tree serving: prefix-tree KV reuse across turns,
copy-on-write fork-on-branch (n>1 sampling), and honest suffix-only
billing. Runs entirely on the mocker (SimRunner) — the sim stream is a
pure function of (prev_token, position), so byte-identity assertions
here pin the same invariants the real runner's A/Bs measure."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.engine.kv_pool import NoSpace, PagePool
from dynamo_tpu.mocker.sim import SimRunner, SimTiming
from dynamo_tpu.runtime.context import Context

PS = 4


# -- kv_pool.fork_table unit coverage ---------------------------------------


def test_fork_table_shares_trunk_and_copies_tail():
    pool = PagePool(16, PS)
    copies = []
    pool.copy_hook = lambda src, dst: copies.append((src, dst))
    pages = pool.alloc(4)
    fork = pool.fork_table(pages, n_shared=3)
    assert fork[:3] == pages[:3]  # trunk shared by reference
    assert fork[3] != pages[3]  # tail is a fresh private page
    assert copies == [(pages[3], fork[3])]  # CoW copy of the tail only
    for p in pages[:3]:
        assert pool.ref[p] == 2
    assert pool.ref[pages[3]] == 1 and pool.ref[fork[3]] == 1
    assert pool.forks == 1


def test_fork_table_release_both_branches_leak_free():
    pool = PagePool(16, PS)
    pages = pool.alloc(4)
    fork = pool.fork_table(pages, n_shared=2)
    pool.release(fork)
    pool.release(pages)
    assert not pool.ref and pool.n_free == 16
    assert sorted(pool.free) == list(range(16))


def test_fork_table_nospace_leaves_parent_untouched():
    pool = PagePool(4, PS)
    pages = pool.alloc(4)  # pool exhausted
    with pytest.raises(NoSpace):
        pool.fork_table(pages, n_shared=2)  # needs 2 fresh tail pages
    assert all(pool.ref[p] == 1 for p in pages)  # no half-applied fork
    assert pool.forks == 0


def test_match_prefix_counts_warm_blocks():
    pool = PagePool(16, PS)
    pages = pool.alloc(2)
    from dynamo_tpu.tokens.hashing import block_hashes

    toks = list(range(20, 28))
    h = block_hashes(toks, PS, None)
    pool.register(pages[0], h[0], None)
    pool.register(pages[1], h[1], h[0])
    pool.release(pages)
    got, hashes = pool.match_prefix(toks + [1, 2])
    assert len(got) == 2 and hashes == h
    assert pool.match_hit_blocks == 2


# -- engine-level helpers ----------------------------------------------------


def _engine(prefix_cache=True, num_pages=512, max_batch=8, **kw):
    runner = SimRunner(num_pages=num_pages, page_size=PS,
                       max_pages_per_seq=64, timing=SimTiming(speed=0.0))
    engine = InferenceEngine(
        runner, max_batch=max_batch, chunk_size=16, decode_steps=4,
        mixed_prefill_tokens=64, enable_prefix_cache=prefix_cache,
        recorder_size=256, **kw,
    )
    return runner, engine


async def _collect(engine, prompt, n=16, temperature=0.0, seed=11,
                   n_choices=1):
    """Stream one request; returns {choice_index: [tokens...]}."""
    streams = {}
    req = {"token_ids": list(prompt),
           "sampling": {"temperature": temperature, "seed": seed,
                        "n": n_choices},
           "stop": {"max_tokens": n, "stop_ids": []}}
    async for item in engine.generate(req, Context()):
        assert item.get("finish_reason") != "error", item
        streams.setdefault(item.get("index", 0), []).extend(item["token_ids"])
    return streams


def _pool_state(pool):
    return (sorted(pool.free), sorted(pool.cached),
            sorted(pool.by_hash.keys()), pool.n_free,
            dict(pool.ref))


# -- session-tree reuse across turns ----------------------------------------


async def test_second_turn_hits_warm_tree_and_stays_byte_identical():
    """Turn 2 extends turn 1's prompt+reply: the warm engine serves the
    shared trunk from registered blocks (reused_prefix_tokens > 0) and
    still emits exactly the cold engine's bytes."""
    turn1 = [3, 1, 4, 1, 5, 9, 2, 6] * 4

    async def run(prefix_cache):
        r, e = _engine(prefix_cache)
        e.start()
        try:
            out1 = (await _collect(e, turn1))[0]
            turn2 = turn1 + out1 + [7, 7, 7, 7]
            out2 = (await _collect(e, turn2))[0]
        finally:
            e.stop()
        return out1, out2, e.scheduler.reused_prefix_tokens, r.stats

    w1, w2, warm_reused, warm_stats = await run(True)
    c1, c2, cold_reused, cold_stats = await run(False)
    assert (w1, w2) == (c1, c2)  # tree reuse never changes bytes
    assert warm_reused > 0 and cold_reused == 0
    # suffix-only billing: the warm engine dispatched fewer real prefill
    # tokens than the cold one by exactly the reused prefix
    saved = (cold_stats["prefill_tokens_real"]
             - warm_stats["prefill_tokens_real"])
    assert saved == warm_reused, (saved, warm_reused)


async def test_tree_hit_blocks_reach_the_worker_digest():
    """Blocks served warm by match_prefix are a total of the pool; their
    reader is the worker digest's `tree` block (the iteration record
    carried a copy no one read, until PR 38)."""
    from dynamo_tpu.runtime.fleet_observer import DigestBuilder

    turn1 = [2, 7, 1, 8] * 6
    _, e = _engine(True)
    e.start()
    try:
        out1 = (await _collect(e, turn1))[0]
        await _collect(e, turn1 + out1 + [9, 9])
    finally:
        e.stop()
    assert e.pool.match_hit_blocks > 0
    tree = DigestBuilder(instance_id=1).build(e)["tree"]
    assert tree["hit_blocks"] == e.pool.match_hit_blocks
    assert 0.0 < tree["hit_rate"] <= 1.0


# -- fork-on-branch (n>1 sampling) ------------------------------------------


async def test_fork_greedy_byte_identity_vs_fresh_and_leak_free():
    """n=3 greedy: every branch must emit exactly the bytes a fresh
    request with the same prompt emits, the fork must be counted, and
    finishing all branches must leave the page pool leak-free."""
    prompt = [5, 3, 8, 2] * 5
    r, e = _engine(True)
    e.start()
    try:
        fresh = (await _collect(e, prompt, n=12))[0]
        streams = await _collect(e, prompt, n=12, n_choices=3)
    finally:
        e.stop()
    assert sorted(streams) == [0, 1, 2]
    for idx, toks in streams.items():
        assert toks == fresh, (idx, toks, fresh)
    assert e.pool.forks == 2  # n=3 → two forked siblings
    assert r.stats["page_copies"] >= 2  # CoW tail copy billed per branch
    pool = e.pool
    assert not pool.ref, pool.ref  # every branch released its pages
    assert pool.n_free == pool.num_pages  # free + LRU-cached, no pins


async def test_fork_shares_trunk_pages_with_parent():
    """While branches decode, the prompt trunk is ref-shared, not
    duplicated: n=4 on a long prompt must allocate far fewer pages than
    four cold requests would."""
    prompt = list(range(30, 30 + 40))  # 10 full pages of trunk
    r, e = _engine(True, num_pages=64)
    e.start()
    try:
        streams = await _collect(e, prompt, n=8, n_choices=4)
    finally:
        e.stop()
    assert sorted(streams) == [0, 1, 2, 3]
    # 4 cold copies would need ~4*12 pages; the tree peak is bounded by
    # trunk + 4 private tails. Leak-free afterwards either way.
    assert not e.pool.ref
    assert e.pool.forks == 3


async def test_fork_with_divergent_sampling_diverges():
    """Seeded non-greedy branches get distinct derived seeds (base+k) so
    the choices explore, like the frontend's n-fan-out does."""
    prompt = [6, 6, 7, 7] * 4
    _, e = _engine(True)
    e.start()
    try:
        streams = await _collect(e, prompt, n=12, temperature=1.0,
                                 seed=21, n_choices=3)
    finally:
        e.stop()
    assert sorted(streams) == [0, 1, 2]
    # the sim stream is seed-independent, so divergence is not observable
    # on the mocker; what IS pinned: all three choices completed with
    # max_tokens tokens and independent page tables (leak-free teardown)
    for toks in streams.values():
        assert len(toks) == 12
    assert not e.pool.ref


async def test_fork_nospace_errors_only_the_branch():
    """When the pool can't fork a sibling, the parent stream must still
    complete; the missing choice surfaces as an indexed error item."""
    prompt = list(range(40, 40 + 32))
    _, e = _engine(True, num_pages=10, max_batch=4)
    e.start()
    try:
        req = {"token_ids": prompt,
               "sampling": {"temperature": 0.0, "seed": 1, "n": 3},
               "stop": {"max_tokens": 8, "stop_ids": []}}
        ok, errs = {}, []
        async for item in e.generate(req, Context()):
            if item.get("finish_reason") == "error":
                errs.append(item)
            else:
                ok.setdefault(item.get("index", 0), []).extend(
                    item["token_ids"])
        assert 0 in ok and ok[0], ok  # parent served
        assert errs, "forks had to fail on a 10-page pool"
        for it in errs:
            assert it.get("index", 0) > 0  # only branches errored
        # a choice either streams tokens or errors, never both: a parent
        # preempted after forking must not re-fork on re-prefill and emit
        # duplicate finishes (which would close the stream early and leak
        # the still-decoding parent's pages)
        assert not set(ok) & {it.get("index", 0) for it in errs}
    finally:
        e.stop()
    assert not e.pool.ref


async def test_abort_tears_down_branches():
    """Cancelling the parent stream mid-decode aborts every forked
    branch too — nothing keeps holding pages."""
    prompt = [9, 8, 7, 6] * 6
    runner, e = _engine(True)
    runner.timing = SimTiming(speed=1.0, decode_base_s=0.02,
                              dispatch_overhead_s=0.0)
    e.start()
    try:
        req = {"token_ids": prompt,
               "sampling": {"temperature": 0.0, "seed": 1, "n": 3},
               "stop": {"max_tokens": 512, "stop_ids": []}}
        gen = e.generate(req, Context())
        got = 0
        async for item in gen:
            if item["token_ids"]:
                got += 1
            if got >= 2:
                break  # drop the stream — engine must see the abort
        await gen.aclose()
        for _ in range(100):
            if not e.scheduler.active and not e.pool.ref:
                break
            await asyncio.sleep(0.05)
    finally:
        e.stop()
    assert not e.scheduler.active
    assert not e.pool.ref, e.pool.ref


# -- scheduler charge accounting --------------------------------------------


def test_adopt_branch_inherits_parent_position():
    from dynamo_tpu.engine.scheduler import Scheduler, SeqState, Sequence

    pool = PagePool(32, PS)
    sched = Scheduler(pool, max_batch=4, chunk_size=64)
    parent = Sequence(request_id="p", prompt=list(range(10, 22)),
                      sampling={}, stop={"max_tokens": 8})
    sched.add(parent)
    plan = sched.step_plan()
    sched.complete_prefill(plan)
    assert parent.state == SeqState.RUNNING
    fork_pages = pool.fork_table(parent.pages,
                                 parent.computed_len // PS)
    branch = Sequence(request_id="p#b1", prompt=list(parent.prompt),
                      sampling={}, stop={"max_tokens": 8},
                      branch_of="p", branch_index=1)
    assert sched.adopt_branch(branch, parent, fork_pages)
    assert branch.state == SeqState.RUNNING
    assert branch.computed_len == parent.computed_len
    assert branch.tokens == parent.tokens
    assert branch.hash_chain == parent.hash_chain
    assert branch in sched.active
    # over max_batch: adoption refuses and releases the forked pages
    free_before = pool.n_free
    extra = [Sequence(request_id=f"x{i}", prompt=[1, 2], sampling={},
                      stop={}) for i in range(3)]
    for s in extra:
        sched.active.append(s)
    p2 = pool.fork_table(parent.pages, parent.computed_len // PS)
    b2 = Sequence(request_id="p#b2", prompt=list(parent.prompt),
                  sampling={}, stop={}, branch_of="p", branch_index=2)
    assert not sched.adopt_branch(b2, parent, p2)
    assert pool.n_free == free_before
