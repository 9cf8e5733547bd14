"""Runtime sanitizer unit + integration tests (satellite 3, PR 13).

Unit coverage per check — transfer guard (trip, allowlist escape, cold
no-op), recompile tripwire, lock-order recorder, asyncio watchdog +
leaked-task audit, page-pool audit — plus the two engine-level
guarantees: a strict sanitizer rides a real tiny-model engine through
warm decode with ZERO violations, and sanitizer-off output is
byte-identical to sanitizer-on (the guard observes, never perturbs).
"""

import asyncio
import threading
import time

import pytest

from dynamo_tpu.engine.kv_pool import PagePool
from dynamo_tpu.runtime.sanitizer import (
    DEFAULT_ALLOWLIST,
    Sanitizer,
    SanitizerViolation,
    env_enabled,
    from_env,
    selftest,
)


def _kinds(san):
    return [v["kind"] for v in san.violations]


# -- arming -----------------------------------------------------------------


def test_env_arming(monkeypatch):
    monkeypatch.delenv("DYN_SAN", raising=False)
    assert not env_enabled() and from_env() is None
    for val in ("1", "true", "ON", "yes"):
        monkeypatch.setenv("DYN_SAN", val)
        assert env_enabled()
    san = from_env(strict=False)
    assert isinstance(san, Sanitizer) and san.strict is False
    monkeypatch.setenv("DYN_SAN", "0")
    assert from_env() is None


def test_selftest_is_green():
    assert selftest() is True


# -- transfer guard ---------------------------------------------------------


def test_transfer_guard_trips_on_implicit_transfer():
    """`float(x[0])` inside a warm transfer_scope must fail loudly, record
    a 'transfer' violation, and re-raise the original jax error (the
    engine's per-step error handling owns failing the sequences)."""
    jnp = pytest.importorskip("jax.numpy")
    san = Sanitizer(strict=False, warmup_steps=0)
    san.mark_warm()
    x = jnp.arange(4)
    with pytest.raises(Exception, match="(?i)transfer"):
        with san.transfer_scope("decode"):
            float(x[0])
    assert _kinds(san) == ["transfer"]
    assert "decode" in san.violations[0]["message"]


def test_transfer_guard_allowlisted_scope_passes():
    jnp = pytest.importorskip("jax.numpy")
    san = Sanitizer(strict=True, warmup_steps=0)  # strict: any slip raises
    san.mark_warm()
    x = jnp.arange(4)
    with san.transfer_scope("decode"):
        with san.allow_transfer("token_readback"):
            assert float(x[0]) == 0.0
        with san.allow_transfer("decode_staging"):
            jnp.asarray([1, 2, 3])
    assert san.ok()
    assert san.counters["allowed_transfers"] == 2


def test_transfer_guard_cold_engine_is_noop():
    """Warmup iterations compile and stage freely — the guard only arms
    once the sanitizer is warm."""
    jnp = pytest.importorskip("jax.numpy")
    san = Sanitizer(strict=True)
    assert not san.report()["warm"]
    with san.transfer_scope("decode"):
        float(jnp.arange(2)[0])  # would trip if armed
    assert san.ok()


def test_allow_transfer_unknown_label_is_violation():
    san = Sanitizer(strict=False, transfer_guard=False)
    with san.allow_transfer("sneaky_new_sync"):
        pass
    assert _kinds(san) == ["allowlist"]
    assert "sneaky_new_sync" in san.violations[0]["message"]
    with pytest.raises(SanitizerViolation):
        with Sanitizer(strict=True).allow_transfer("sneaky_new_sync"):
            pass


def test_default_allowlist_is_the_documented_set():
    # docs/static_analysis.md carries one row per label; keep them in sync
    assert DEFAULT_ALLOWLIST == frozenset({
        "decode_staging", "spec_staging", "verify_staging",
        "sampling_staging", "token_readback", "embed_readback",
        "draft_readback", "kv_tier_io", "weight_reload",
    })


# -- recompile tripwire -----------------------------------------------------


class _Fam:
    def __init__(self, variants):
        self.variants = variants
        self.calls = 0


class _FakeRunner:
    def __init__(self):
        self._families = {"decode": _Fam(2), "prefill": _Fam(3)}

    def compile_families(self):
        return self._families


def test_recompile_tripwire_fires_once_per_leak():
    san = Sanitizer(strict=False, transfer_guard=False, warmup_steps=2)
    r = _FakeRunner()
    san.note_step(r)
    assert not san.report()["warm"]
    san.note_step(r)  # hits warmup_steps: baseline frozen here
    assert san.report()["warm"]
    san.note_step(r)
    assert san.ok()

    r._families["decode"].variants = 3  # shape churn after warmup
    san.note_step(r)
    assert _kinds(san) == ["recompile"]
    assert "2->3" in san.violations[0]["message"]
    san.note_step(r)  # baseline advanced: the same leak reports once
    assert len(san.violations) == 1

    r._families["guided"] = _Fam(1)  # whole new family after warmup
    san.note_step(r)
    assert _kinds(san) == ["recompile", "recompile"]
    assert "guided" in san.violations[1]["message"]


def test_recompile_tripwire_strict_raises_and_sim_runner_noop():
    san = Sanitizer(strict=True, transfer_guard=False, warmup_steps=1)
    r = _FakeRunner()
    san.note_step(r)
    r._families["decode"].variants += 1
    with pytest.raises(SanitizerViolation, match="recompile"):
        san.note_step(r)

    from dynamo_tpu.engine.runner_api import Runner

    class _NoFamilies(Runner):  # a SimRunner compiles nothing: a no-op
        pass

    san2 = Sanitizer(strict=True, transfer_guard=False, warmup_steps=1)
    for _ in range(8):
        san2.note_step(_NoFamilies())
    assert san2.ok() and san2.report()["steps"] == 8


def test_recompile_tripwire_exempts_admission_families():
    """A new prefill ('forward') bucket after warmup is admission-boundary
    work — a first-of-its-size prompt or a preempted sequence re-prefilling
    past its old bucket — and must be counted, not raised, even in strict
    mode (found by a live-worker drive: an over-context request preempted,
    re-prefilled into a bigger bucket, and killed the step thread)."""
    san = Sanitizer(strict=True, transfer_guard=False, warmup_steps=1)
    r = _FakeRunner()
    r._families["forward"] = _Fam(2)
    san.note_step(r)
    r._families["forward"].variants = 3  # admission growth: soft
    san.note_step(r)
    assert san.ok()
    assert san.counters["admission_recompiles"] == 1
    r._families["decode"].variants += 1  # steady-state growth: still hard
    with pytest.raises(SanitizerViolation, match="recompile"):
        san.note_step(r)


# -- lock-order recorder ----------------------------------------------------


def test_lock_cycle_detected_with_full_path():
    san = Sanitizer(strict=False, transfer_guard=False)
    a = san.wrap_lock(threading.Lock(), "engine.guided_cache")
    b = san.wrap_lock(threading.Lock(), "engine.lifter")
    with a, b:
        pass
    assert san.ok()  # one order is fine, however often
    with a, b:
        pass
    assert san.ok()
    with b, a:  # opposite order closes the cycle
        pass
    v = [v for v in san.violations if v["kind"] == "lock_order"]
    assert len(v) == 1
    assert ("engine.guided_cache -> engine.lifter -> engine.guided_cache"
            in v[0]["message"])
    assert "closed it" in v[0]["message"]


def test_lock_cycle_three_nodes_and_strict_raise():
    san = Sanitizer(strict=True, transfer_guard=False)
    a = san.wrap_lock(threading.Lock(), "A")
    b = san.wrap_lock(threading.Lock(), "B")
    c = san.wrap_lock(threading.Lock(), "C")
    with a, b:
        pass
    with b, c:
        pass
    with pytest.raises(SanitizerViolation, match="A -> B -> C -> A"):
        with c:  # the raise inside the body still runs c's __exit__
            a.acquire()
    a.release()  # underlying lock was taken before the recorder raised


def test_tracked_lock_is_drop_in():
    san = Sanitizer(strict=True, transfer_guard=False)
    lk = san.wrap_lock(threading.Lock(), "L")
    assert lk.acquire(blocking=False)
    assert lk.locked()
    assert not lk.acquire(blocking=False)  # held: non-blocking fails clean
    lk.release()
    assert not lk.locked()
    assert san.counters["lock_acquires"] == 1  # failed acquire not counted


# -- asyncio watchdog + leaked-task audit -----------------------------------


async def test_watchdog_lag_is_a_gauge_not_a_failure():
    san = Sanitizer(strict=True, transfer_guard=False,
                    watchdog_interval_s=0.01, watchdog_lag_s=0.05)
    san.start_watchdog()
    await asyncio.sleep(0.03)
    time.sleep(0.2)  # deliberately stall the loop past the threshold
    await asyncio.sleep(0.05)
    await san.stop_watchdog()
    assert san.loop_lag_max_s > 0.05
    # recorded even under strict — but never raised (benign causes exist)
    assert "loop_lag" in _kinds(san)
    assert san.report()["loop_lag_max_ms"] > 50


async def test_leaked_task_audit_names_the_leak():
    from dynamo_tpu.runtime.tasks import spawn_tracked

    san = Sanitizer(strict=False, transfer_guard=False)
    ev = asyncio.Event()

    async def hang():
        await ev.wait()

    t = spawn_tracked(hang(), name="unit-leaked-task")
    await asyncio.sleep(0)
    try:
        leaked = san.audit_tasks()
        assert "unit-leaked-task" in leaked
        assert _kinds(san) == ["leaked_task"]
        assert "unit-leaked-task" in san.violations[0]["message"]
    finally:
        ev.set()
        await t
    # once done, the same audit is clean (strict proves no raise)
    assert Sanitizer(strict=True).audit_tasks() == []


async def test_watchdog_itself_never_audits_as_leak():
    san = Sanitizer(strict=True, transfer_guard=False,
                    watchdog_interval_s=0.01)
    san.start_watchdog()
    await asyncio.sleep(0.03)
    assert san.audit_tasks() == []  # retained on self, not spawn_tracked
    await san.stop_watchdog()


# -- page-pool audit --------------------------------------------------------


def test_pool_audit_clean_and_leak_at_teardown():
    pool = PagePool(8, 4)
    san = Sanitizer(strict=False, transfer_guard=False)
    san.audit_pool(pool, live_seqs=0)
    assert san.ok()
    pages = pool.alloc(2)
    san.audit_pool(pool, live_seqs=1)  # a live sequence owns them: fine
    assert san.ok()
    san.audit_pool(pool, live_seqs=0)
    assert _kinds(san) == ["pool"]
    assert "leaked at teardown" in san.violations[0]["message"]
    pool.release(pages)


def test_pool_audit_hash_desync_and_stray_pin():
    pool = PagePool(8, 4)
    san = Sanitizer(strict=False, transfer_guard=False)
    pool.by_hash[1234] = 5  # planted desync: no matching hash_of entry
    pool.pinned.add(999)  # pinned hash that maps to no registered page
    san.audit_pool(pool, live_seqs=0)
    kinds = _kinds(san)
    assert kinds.count("pool") >= 2
    msgs = " | ".join(v["message"] for v in san.violations)
    assert "desync" in msgs and "pinned" in msgs


def test_pool_audit_partition_overlap():
    pool = PagePool(8, 4)
    pages = pool.alloc(1)
    pool.free.append(pages[0])  # planted: same page free AND referenced
    san = Sanitizer(strict=False, transfer_guard=False)
    san.audit_pool(pool, live_seqs=1)
    assert any("two states" in v["message"] for v in san.violations)


# -- engine integration: strict ride-along + off-path byte identity ---------


@pytest.fixture(scope="module")
def tiny_runner():
    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config

    return ModelRunner(
        get_config("tiny"),
        num_pages=64,
        page_size=4,
        max_pages_per_seq=16,
        decode_buckets=(1, 2, 4, 8),
        prefill_buckets=(8, 16, 32),
    )


def _req(prompt, max_tokens=6):
    return {
        "token_ids": prompt,
        "sampling": {"temperature": 0.0, "seed": 0},
        "stop": {"max_tokens": max_tokens, "stop_ids": []},
    }


async def _collect(engine, req):
    from dynamo_tpu.runtime.context import Context

    toks = []
    async for item in engine.generate(req, Context()):
        toks.extend(item["token_ids"])
    return toks


async def test_sanitizer_on_engine_clean_and_off_path_byte_identical(
    tiny_runner,
):
    """The acceptance pair: (a) a STRICT sanitizer rides the real tiny
    model through warm, guarded decode dispatches with zero violations —
    every implicit transfer in the hot path sits inside a named allowlist
    scope; (b) tokens with the sanitizer off are byte-identical to
    sanitizer on, so the guard observes without perturbing."""
    from dynamo_tpu.engine.engine import InferenceEngine

    prompts = [[5, 6, 7, 8, 9], [9, 8, 7, 6, 5], [1, 2, 3, 4, 5]]

    eng_off = InferenceEngine(tiny_runner, max_batch=8, chunk_size=16)
    assert eng_off.sanitizer is None  # off is the default (DYN_SAN unset)
    eng_off.start()
    try:
        baseline = [await _collect(eng_off, _req(p)) for p in prompts]
    finally:
        eng_off.stop()
    assert all(len(t) == 6 for t in baseline)

    san = Sanitizer(strict=True, warmup_steps=3)
    eng_on = InferenceEngine(
        tiny_runner, max_batch=8, chunk_size=16, sanitizer=san,
    )
    assert eng_on.sanitizer is san
    eng_on.start()
    try:
        # warm pass compiles the buckets; the guard arms at warmup_steps
        await _collect(eng_on, _req([4, 4, 4, 4, 4]))
        guarded = [await _collect(eng_on, _req(p)) for p in prompts]
    finally:
        eng_on.stop()  # runs the strict pool audit too

    assert guarded == baseline  # byte-identical token streams
    rep = san.report()
    assert rep["ok"], rep
    assert rep["warm"] and rep["steps"] > 3
    assert san.counters["allowed_transfers"] > 0  # scopes actually ran


async def test_sanitize_flag_builds_engine_sanitizer(tiny_runner):
    from dynamo_tpu.engine.engine import InferenceEngine

    eng = InferenceEngine(tiny_runner, max_batch=4, chunk_size=16,
                          sanitize=True)
    assert eng.sanitizer is not None
    # fail-loud by default (ASan-style); fleet-sim opts into strict=False
    assert eng.sanitizer.strict is True
