"""Runtime sanitizer: cheap always-on invariant checks for serving.

The static side (dynlint's interprocedural pass) proves what it can see
in the AST; this module is the dynamic complement, armed with
``DYN_SAN=1`` (or ``--sanitize`` on the worker / mocker CLIs):

- **transfer guard** — wraps the steady-state decode / spec-verify
  dispatches in ``jax.transfer_guard("disallow")`` once the engine is
  warm, so any *implicit* device↔host sync that creeps into the step
  loop fails loudly at the offending line instead of silently serializing
  the pipeline. Known sync points (input staging, the one bulk token
  readback, embed readback) run inside named :meth:`Sanitizer.allow_transfer`
  scopes checked against an explicit allowlist — an unnamed scope is
  itself a violation, so the allowlist IS the documentation of every
  sanctioned transfer (see docs/static_analysis.md).
- **recompile tripwire** — after ``warmup_steps`` engine iterations the
  compiled-family variant counts (`ModelRunner.compile_families()`) must be
  frozen; any new family or variant afterwards is a compile-cache leak
  (shape churn) and fires a violation.
- **lock-order recorder** — :meth:`wrap_lock` proxies a lock and records
  the held-before graph per acquisition; an edge that closes a cycle
  reports the full path with acquisition sites (the dynamic twin of
  dynlint DYN-R007, which proves the static subset).
- **asyncio watchdog** — samples event-loop lag (a gauge, never fatal)
  and audits the `spawn_tracked` registry for still-running fire-and-
  forget tasks at shutdown.
- **page-pool audit** — free/ref/cached must partition the pool
  (fork_table refcounts included); with no live sequences, `ref` must be
  empty or pages leaked.

Violations raise :class:`SanitizerViolation` when ``strict`` (unit
tests), or accumulate on :attr:`Sanitizer.violations` for a report block
(fleet-sim chaos runs assert the list is empty at teardown). Everything
here is allocation-light; the measured steady-state overhead is in
docs/perf_notes.md.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import os
import sys
import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

log = logging.getLogger("dynamo_tpu.runtime.sanitizer")


class SanitizerViolation(RuntimeError):
    """An invariant the sanitizer enforces was broken (strict mode)."""


#: Every sanctioned implicit-transfer site, by label. Adding a label here
#: is a reviewed act: the docs table in docs/static_analysis.md must gain
#: the matching row explaining WHY the sync is at a request/iteration
#: boundary rather than inside the steady-state loop.
DEFAULT_ALLOWLIST = frozenset({
    "decode_staging",    # per-dispatch int pack + token h2d (model_runner)
    "spec_staging",      # draft-loop tok/pos/table staging
    "verify_staging",    # ragged verify flat-token + metadata staging
    "sampling_staging",  # SamplingParams host->device rows
    "token_readback",    # the ONE bulk d2h sync per fused dispatch
    "draft_readback",    # device n-gram ring proposal d2h (one per spec
                         # iteration, replacing the host history scan)
    "embed_readback",    # request-boundary embedding .tolist
    "kv_tier_io",        # G2/G3 onboarding / offload block copies
    "weight_reload",     # RL weight swap (paused engine, not steady state)
})

#: Compile families that grow at the ADMISSION boundary, not in the warm
#: decode loop: a new prompt-length bucket (first request of that size, or
#: a preempted sequence re-prefilling past its old bucket) legitimately
#: compiles a new prefill variant long after warmup. Growth here is
#: counted and logged once per family, never a violation — mirroring the
#: transfer-guard policy that leaves prefill/mixed dispatch unguarded
#: (docs/static_analysis.md). Steady-state families (decode_loop, mixed,
#: ragged, draft) stay frozen.
ADMISSION_FAMILIES = frozenset({"forward"})


def env_enabled() -> bool:
    return os.environ.get("DYN_SAN", "").lower() in ("1", "true", "on", "yes")


def from_env(**kwargs) -> Optional["Sanitizer"]:
    """Build a Sanitizer iff DYN_SAN is set (the worker/mocker default)."""
    return Sanitizer(**kwargs) if env_enabled() else None


class _TrackedLock:
    """Lock proxy recording acquisition order into the owning Sanitizer.

    Supports the context-manager protocol plus acquire/release/locked so
    it drops in for `threading.Lock` at every engine call site. Non-
    blocking and timeout acquires record only on success.
    """

    __slots__ = ("_lock", "name", "_san")

    def __init__(self, lock, name: str, san: "Sanitizer"):
        self._lock = lock
        self.name = name
        self._san = san

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._lock.acquire(blocking, timeout)
        if got:
            self._san._note_acquire(self.name)
        return got

    def release(self) -> None:
        self._san._note_release(self.name)
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()


class Sanitizer:
    def __init__(
        self,
        *,
        strict: bool = True,
        allowlist: Iterable[str] = DEFAULT_ALLOWLIST,
        transfer_guard: bool = True,
        warmup_steps: int = 16,
        watchdog_interval_s: float = 0.05,
        watchdog_lag_s: float = 0.25,
    ):
        self.strict = strict
        self.allowlist = frozenset(allowlist)
        self.transfer_guard = transfer_guard
        self.warmup_steps = warmup_steps
        self.watchdog_interval_s = watchdog_interval_s
        self.watchdog_lag_s = watchdog_lag_s
        self.violations: List[Dict[str, Any]] = []
        self._vlock = threading.Lock()  # guards violations (multi-thread)
        # recompile tripwire
        self._steps = 0
        self._warm = False
        self._warm_variants: Dict[str, int] = {}
        # lock-order recorder: name -> {successor: (lock_a_site,)} edges;
        # held stacks are per-thread (the engine step thread and asyncio
        # callbacks both take guided locks)
        self._edges: Dict[str, Dict[str, str]] = {}
        self._held = threading.local()
        self._graph_lock = threading.Lock()
        # watchdog
        self._watchdog_task: Optional[asyncio.Task] = None
        self.loop_lag_max_s = 0.0
        self.counters: Dict[str, int] = {
            "steps": 0, "allowed_transfers": 0, "lock_acquires": 0,
        }

    # -- violations --------------------------------------------------------
    def _violation(self, kind: str, message: str) -> None:
        with self._vlock:
            self.violations.append({"kind": kind, "message": message})
        if self.strict:
            raise SanitizerViolation(f"[{kind}] {message}")
        log.warning("sanitizer violation [%s]: %s", kind, message)

    def ok(self) -> bool:
        return not self.violations

    def report(self) -> Dict[str, Any]:
        return {
            "ok": self.ok(),
            "violations": list(self.violations),
            "steps": self._steps,
            "warm": self._warm,
            "loop_lag_max_ms": round(self.loop_lag_max_s * 1e3, 3),
            "counters": dict(self.counters),
        }

    # -- transfer guard ----------------------------------------------------
    @contextlib.contextmanager
    def transfer_scope(self, where: str = "step"):
        """Disallow implicit transfers for the duration (warm engine only
        — warmup iterations compile and stage freely). The violation is
        recorded AND the original error re-raised: the dispatch it broke
        cannot be completed, and the engine's per-step error handling
        owns failing the affected sequences."""
        jax = sys.modules.get("jax")
        if jax is None or not (self.transfer_guard and self._warm):
            # never import jax ourselves: mocker processes run the whole
            # engine jax-free and the sanitizer must not change that
            yield
            return
        try:
            with jax.transfer_guard("disallow"):
                yield
        except SanitizerViolation:
            raise
        except Exception as e:
            if "transfer" in str(e).lower():
                with self._vlock:
                    self.violations.append({
                        "kind": "transfer",
                        "message": f"implicit transfer in {where}: {e}",
                    })
                log.error("sanitizer: implicit transfer in %s: %s", where, e)
            raise

    @contextlib.contextmanager
    def allow_transfer(self, label: str):
        """Named escape hatch for a known sync point. Labels outside the
        allowlist are violations — the allowlist is the reviewed budget
        of sanctioned transfers, not a convenience."""
        if label not in self.allowlist:
            self._violation(
                "allowlist",
                f"transfer scope {label!r} is not in the sanitizer "
                f"allowlist; add it to DEFAULT_ALLOWLIST *and* the docs "
                "table, or remove the sync",
            )
            yield  # non-strict: record, then let it run
            return
        self.counters["allowed_transfers"] += 1
        jax = sys.modules.get("jax")
        if jax is None or not (self.transfer_guard and self._warm):
            yield
            return
        with jax.transfer_guard("allow"):
            yield

    # -- layout guard ------------------------------------------------------
    def check_layouts(self, runner: Any) -> int:
        """Diff live ``jax.Array.sharding`` for every row of the runner's
        statically-derived layout table (ModelRunner.layout_table —
        ShardingPolicy over parallel/mesh.py's canonical spec tables)
        against the declared NamedSharding. Any inequivalence is a HARD
        violation carrying both specs: the array was silently re-placed,
        which means an implicit reshard/all-gather is hiding in the path
        that produced it — the dynamic twin of dynlint DYN-S001/S003.
        Runs once at warm-path entry (note_step), when params and pools
        are in their steady-state placement. Runners without a
        layout_table (mocker SimRunner — the whole fleet sim runs
        jax-free) no-op. Returns the number of rows checked."""
        # no jax gate needed: the guard only reads attributes the arrays
        # already carry, and jax-free runners (SimRunner) simply have no
        # layout_table
        table_fn = getattr(runner, "layout_table", None)
        if table_fn is None:
            return 0
        checked = 0
        for name, arr, want in table_fn():
            live = getattr(arr, "sharding", None)
            if live is None:
                continue
            checked += 1
            try:
                same = live.is_equivalent_to(want, arr.ndim)
            except Exception:
                same = live == want
            if not same:
                self._violation(
                    "layout",
                    f"{name}: live sharding {live} diverges from the "
                    f"declared spec {want.spec} on mesh "
                    f"{dict(want.mesh.shape)} — the array was silently "
                    "re-placed (implicit reshard/all-gather) after the "
                    "policy applied the canonical table",
                )
        self.counters["layout_checked"] = checked
        return checked

    # -- recompile tripwire ------------------------------------------------
    def mark_warm(self) -> None:
        self._warm = True

    def note_step(self, runner: Any = None) -> None:
        """Called once per engine iteration (step thread). Arms the
        transfer guard and freezes the compiled-family baseline after
        `warmup_steps`; any later growth is a compile-cache leak."""
        self._steps += 1
        self.counters["steps"] = self._steps
        fams = runner.compile_families() if runner is not None else {}
        variants = {name: fam.variants for name, fam in fams.items()}
        if not self._warm:
            if self._steps >= self.warmup_steps:
                self.mark_warm()
                self._warm_variants = variants
                # warm-path entry: params/pools are in steady-state
                # placement — snapshot and diff their live layouts once
                if runner is not None:
                    self.check_layouts(runner)
            return
        for name, n in variants.items():
            base = self._warm_variants.get(name)
            # update the baseline BEFORE reporting so a non-strict run
            # logs each leak once instead of every subsequent step
            self._warm_variants[name] = n
            if name in ADMISSION_FAMILIES:
                if base is not None and n > base:
                    self.counters["admission_recompiles"] = (
                        self.counters.get("admission_recompiles", 0) + 1
                    )
                    log.info(
                        "admission-boundary family %r grew %d->%d variants "
                        "(step %d) — new prompt bucket, not a warm-loop leak",
                        name, base, n, self._steps,
                    )
                continue
            if base is None:
                self._violation(
                    "recompile",
                    f"new compiled family {name!r} appeared after warmup "
                    f"(step {self._steps})",
                )
            elif n > base:
                self._violation(
                    "recompile",
                    f"compiled family {name!r} grew {base}->{n} variants "
                    f"after warmup (step {self._steps}) — shape churn in "
                    "the steady-state loop",
                )

    # -- lock-order recorder -----------------------------------------------
    def wrap_lock(self, lock, name: str) -> _TrackedLock:
        return _TrackedLock(lock, name, self)

    def _held_stack(self) -> List[str]:
        st = getattr(self._held, "stack", None)
        if st is None:
            st = self._held.stack = []
        return st

    def _note_acquire(self, name: str) -> None:
        self.counters["lock_acquires"] += 1
        st = self._held_stack()
        if st:
            outer = st[-1]
            if outer != name:
                with self._graph_lock:
                    fresh = name not in self._edges.setdefault(outer, {})
                    if fresh:
                        self._edges[outer][name] = (
                            threading.current_thread().name
                        )
                        cycle = self._find_cycle(name, outer)
                    else:
                        cycle = None
                if fresh and cycle:
                    self._violation(
                        "lock_order",
                        "lock acquisition order cycle: "
                        + " -> ".join(cycle)
                        + f" (edge {outer!r} -> {name!r} closed it on "
                        f"thread {threading.current_thread().name!r})",
                    )
        st.append(name)

    def _note_release(self, name: str) -> None:
        st = self._held_stack()
        # out-of-order release is legal (threading allows it); drop the
        # newest matching entry
        for i in range(len(st) - 1, -1, -1):
            if st[i] == name:
                del st[i]
                break

    def _find_cycle(self, start: str, target: str) -> Optional[List[str]]:
        """Path start ->* target in the held-before graph (caller holds
        _graph_lock); with the new target->start edge that is a cycle."""
        path: List[str] = []
        seen = set()

        def dfs(node: str) -> bool:
            if node == target:
                path.append(node)
                return True
            if node in seen:
                return False
            seen.add(node)
            for nxt in self._edges.get(node, {}):
                if dfs(nxt):
                    path.append(node)
                    return True
            return False

        if dfs(start):
            path.reverse()  # start ... target; closing edge returns to start
            return path + [start]
        return None

    # -- asyncio watchdog --------------------------------------------------
    def start_watchdog(self) -> asyncio.Task:
        """Start the event-loop lag sampler (call from the serving loop).
        Plain create_task retained on self — deliberately NOT
        spawn_tracked, so audit_tasks never reports the watchdog
        itself."""
        if self._watchdog_task is None or self._watchdog_task.done():
            self._watchdog_task = asyncio.get_running_loop().create_task(
                self._watch(), name="dyn-san-watchdog"
            )
        return self._watchdog_task

    async def stop_watchdog(self) -> None:
        t = self._watchdog_task
        if t is not None and not t.done():
            t.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await t
        self._watchdog_task = None

    async def _watch(self) -> None:
        loop = asyncio.get_running_loop()
        interval = self.watchdog_interval_s
        while True:
            t0 = loop.time()
            await asyncio.sleep(interval)
            lag = loop.time() - t0 - interval
            if lag > self.loop_lag_max_s:
                self.loop_lag_max_s = lag
            if lag > self.watchdog_lag_s:
                # a gauge, not a failure: lag has benign causes (cold
                # imports, CI noise) — record without raising even in
                # strict mode
                with self._vlock:
                    self.violations.append({
                        "kind": "loop_lag",
                        "message": f"event loop stalled {lag*1e3:.0f} ms "
                                   f"(threshold {self.watchdog_lag_s*1e3:.0f} ms)",
                    })
                log.warning("sanitizer: event loop stalled %.0f ms", lag * 1e3)

    def audit_tasks(self) -> List[str]:
        """Leaked fire-and-forget audit (shutdown): every spawn_tracked
        task should be done once its owner stopped. Returns the leaked
        task names (and files a violation if any)."""
        from dynamo_tpu.runtime import tasks as _tasks

        leaked = sorted(
            t.get_name() for t in _tasks._TRACKED if not t.done()
        )
        if leaked:
            self._violation(
                "leaked_task",
                f"{len(leaked)} tracked task(s) still running at audit: "
                + ", ".join(leaked[:8]),
            )
        return leaked

    # -- page-pool audit ---------------------------------------------------
    def audit_pool(self, pool, live_seqs: int = 0) -> None:
        """PagePool partition/refcount invariants at request teardown or
        engine stop. fork_table-aware: forked pages legitimately carry
        ref > 1; what must never happen is a page in two states at once,
        a non-positive refcount, or allocated pages with no live
        sequence to own them."""
        free = set(pool.free)
        refd = set(pool.ref)
        cached = set(pool.cached)
        overlap = (free & refd) | (free & cached) | (refd & cached)
        if overlap:
            self._violation(
                "pool",
                f"pages in two states at once: {sorted(overlap)[:8]}",
            )
        missing = set(range(pool.num_pages)) - free - refd - cached
        if missing:
            self._violation(
                "pool",
                f"pages lost from the pool (not free/ref/cached): "
                f"{sorted(missing)[:8]}",
            )
        bad_ref = {p: c for p, c in pool.ref.items() if c <= 0}
        if bad_ref:
            self._violation(
                "pool", f"non-positive refcounts: {bad_ref}"
            )
        if live_seqs == 0 and refd:
            self._violation(
                "pool",
                f"{len(refd)} page(s) still referenced with no live "
                f"sequences — leaked at teardown: {sorted(refd)[:8]}",
            )
        for h, p in pool.by_hash.items():
            if pool.hash_of.get(p) != h:
                self._violation(
                    "pool",
                    f"hash index desync: by_hash[{h}]={p} but "
                    f"hash_of[{p}]={pool.hash_of.get(p)}",
                )
        stray_pins = set(pool.pinned) - set(pool.by_hash)
        if stray_pins:
            self._violation(
                "pool",
                f"pinned hashes with no registered page: "
                f"{sorted(stray_pins)[:8]}",
            )


def selftest() -> bool:
    """Cheap jax-free self-check used by scripts/check_tier1.py to report
    `sanitizer_ok`: lock-cycle detection, allowlist rejection, and the
    violation plumbing must all work in-process."""
    san = Sanitizer(strict=False, transfer_guard=False)
    a = san.wrap_lock(threading.Lock(), "A")
    b = san.wrap_lock(threading.Lock(), "B")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    assert any(v["kind"] == "lock_order" for v in san.violations), \
        "lock cycle not detected"
    n = len(san.violations)
    with san.allow_transfer("not_a_real_label"):
        pass
    assert any(v["kind"] == "allowlist" for v in san.violations[n:]), \
        "allowlist breach not detected"
    strict = Sanitizer(strict=True)
    try:
        strict._violation("selftest", "must raise")
    except SanitizerViolation:
        pass
    else:
        raise AssertionError("strict mode did not raise")

    # layout guard plumbing, still jax-free: a runner WITHOUT a
    # layout_table must no-op (the fleet sim's SimRunner path), and a
    # mismatched table row must fire a "layout" violation with both
    # sides in the message
    class _Placement:
        def __init__(self, tag):
            self.tag = tag
            self.spec = tag
            self.mesh = type("M", (), {"shape": {}})()

        def is_equivalent_to(self, other, ndim):
            return self.tag == other.tag

        def __str__(self):
            return self.tag

    class _Arr:
        ndim = 2

        def __init__(self, tag):
            self.sharding = _Placement(tag)

    class _Runner:
        def layout_table(self):
            return [("params/good", _Arr("P('model')"),
                     _Placement("P('model')")),
                    ("params/drifted", _Arr("P()"),
                     _Placement("P('model')"))]

    lay = Sanitizer(strict=False, transfer_guard=False)
    assert lay.check_layouts(object()) == 0, "table-less runner must no-op"
    assert lay.check_layouts(_Runner()) == 2
    bad = [v for v in lay.violations if v["kind"] == "layout"]
    assert len(bad) == 1 and "params/drifted" in bad[0]["message"], \
        "layout drift not detected"
    return True
