"""Predictive KV prefetch plane: router-hinted tier promotion.

The KV router scores a request against every worker's device AND
lower-tier (G2/G3/G4) residency before dispatch, so it knows what the
chosen worker will need seconds before the engine does. This module
spends that lead time: the router emits a `kv_prefetch` hint over the
request plane ahead of the request itself, and the worker's
PrefetchManager promotes the hinted blocks up the KVBM ladder while the
request is still queueing —

    G3 → G2: file reads ride the disk pool's existing writer thread
             (DiskKvPool.read_block_async), so the step thread never
             blocks on file IO; results land back on the step thread
             via the engine inbox.
    G2 → G1: `runner.import_pages` on the step thread, between
             iterations (the import primitive mutates device pool state
             and is only safe serialized with steps — same constraint
             the synchronous admission-time onboard lives under).

Promoted pages are registered into the PagePool and released into its
reusable-cache set *pinned*: eviction skips them, and the scheduler's
ordinary `match_prefix` claims them when the hinted request arrives —
no new scheduler path, the synchronous onboard candidates simply shrink
to zero. Everything is governed by:

    max_inflight     cap on concurrent G3→G2 reads in flight
    bandwidth_mbps   token-bucket budget on promoted bytes/s (0 = off)
    hint_ttl_s       a hinted block not yet promoted when the TTL fires
                     is cancelled (the request never arrived)
    pin_ttl_s        a promoted-but-unclaimed block is unpinned after
                     this long (back to plain LRU-evictable cache)

Late arrivals (request lands mid-promote) fall back to the untouched
synchronous onboard path: promotion COPIES from G2 (the tier keeps its
block), and a duplicate device import resolves through the PagePool's
register() dedup, so the result is byte-identical either way.

Accounting is request-id free: hits fire from the PagePool's claim hook
(a pinned hash claimed by match_prefix), lates from the engine's
synchronous onboard overlapping an in-flight promotion, cancels from
TTL expiry. Counters surface through runtime/metrics.py.
"""

from __future__ import annotations

import logging
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from dynamo_tpu.runtime import tracing

from .quant import pair_nbytes, quantized_ratio

log = logging.getLogger("dynamo_tpu.kvbm.prefetch")

# job states
QUEUED = "queued"        # accepted, waiting for budget / in-flight slot
READING = "reading"      # G3→G2 file read in flight on the disk thread
PROMOTED = "promoted"    # registered + pinned in the device pool


class _Job:
    __slots__ = ("h", "parent", "state", "t0", "deadline", "pin_deadline",
                 "tp")

    def __init__(self, h: int, parent: Optional[int], t0: float, deadline: float):
        self.h = h
        self.parent = parent
        self.state = QUEUED
        self.t0 = t0
        self.deadline = deadline
        self.pin_deadline = 0.0
        self.tp = None  # traceparent of the hinting route span, if any


class PrefetchManager:
    """Owned by the engine; every method runs on the engine step thread
    unless noted. The only cross-thread entry is the disk-read callback,
    which posts back through the engine inbox."""

    def __init__(
        self,
        engine,
        *,
        max_inflight: int = 4,
        bandwidth_mbps: float = 0.0,  # 0 = unlimited
        hint_ttl_s: float = 10.0,
        pin_ttl_s: float = 5.0,
        metrics=None,
        clock=time.monotonic,  # injectable for deterministic TTL tests
        sim_block_bytes: int = 1 << 18,  # budget charge for hash-only blocks
    ):
        self.engine = engine
        self.pool = engine.pool
        self.tiered = engine.host_pool  # TieredKv (G2 [+G3 +G4])
        self.max_inflight = max(1, int(max_inflight))
        self.hint_ttl_s = float(hint_ttl_s)
        self.pin_ttl_s = float(pin_ttl_s)
        self.sim_block_bytes = int(sim_block_bytes)
        self._clock = clock
        self._bps = float(bandwidth_mbps) * 1e6
        self._limited = self._bps > 0
        # token bucket with one-block overdraft: dispatch is gated on a
        # non-negative balance, charges land at completion, refill in tick()
        self._budget_bytes = self._bps * 0.1 if self._limited else 0.0
        self._budget_burst = max(self._bps * 0.5, float(self.sim_block_bytes))
        self._last_refill = clock()

        self._jobs: "OrderedDict[int, _Job]" = OrderedDict()  # hash -> job
        self._queue: deque = deque()  # hashes awaiting dispatch (FIFO)
        self._reading: set = set()  # hashes with a disk read in flight

        self.stats: Dict[str, Any] = {
            "hints": 0,            # hint messages accepted
            "hinted_blocks": 0,    # blocks enqueued for promotion
            "promoted": 0,         # blocks registered + pinned in G1
            "hits": 0,             # pinned blocks claimed by a request
            "late": 0,             # sync onboard won the race mid-promote
            "cancelled": 0,        # hint/pin TTL expiries
            "dup": 0,              # import lost the register() dedup race
            "no_space": 0,         # device pool full, left to sync path
            "lost": 0,             # block evicted out from under the job
            "bytes_promoted": 0,
            # per-hop split at the ACTUAL stored width (int8+scales tiers
            # move ~0.52x the dense bytes): G3→G2 file-read bytes vs
            # G2→G1 device-import bytes (always dense — the import
            # boundary dequantizes)
            "bytes_promoted_g3": 0,
            "bytes_promoted_g2": 0,
            "bytes_promoted_g4": 0,  # G4→G2 object-store fetch bytes
            "reading_peak": 0,
            "promote_latency_sum_s": 0.0,
        }
        if metrics is None:
            from dynamo_tpu.runtime.metrics import make_metrics

            metrics = make_metrics("worker")
        self.bind_metrics(metrics)
        self.pool.claim_hook = self._on_claim

    def bind_metrics(self, metrics) -> None:
        """Re-home the counters onto a shared hierarchy. The worker calls
        this with runtime.metrics at serve time so the status-port
        /metrics renders them — the engine-built default lives in its own
        registry that no HTTP surface exports."""
        node = metrics.child(dynamo_component="kv_prefetch")
        self._m_hits = node.counter(
            "kv_prefetch_hits_total", "prefetched blocks claimed by a request")
        self._m_late = node.counter(
            "kv_prefetch_late_total",
            "blocks onboarded synchronously while their promotion was in flight")
        self._m_cancelled = node.counter(
            "kv_prefetch_cancelled_total", "hinted blocks expired by TTL unclaimed")
        self._m_bytes = node.counter(
            "kv_prefetch_bytes_total", "bytes promoted up the KV ladder")

    # -- hint ingress (engine inbox op "prefetch") ---------------------------
    def on_hint(self, hint: Dict[str, Any]) -> None:
        hashes = [int(h) for h in (hint.get("hashes") or [])]
        parents = list(hint.get("parents") or [])
        if not hashes:
            return
        self.stats["hints"] += 1
        now = self._clock()
        hint_tp = hint.get("traceparent")
        for i, h in enumerate(hashes):
            if h in self._jobs or h in self.pool.by_hash:
                continue  # already warm or already being promoted
            parent = parents[i] if i < len(parents) else None
            parent = int(parent) if parent is not None else None
            job = _Job(h, parent, now, now + self.hint_ttl_s)
            job.tp = hint_tp
            if h in self._reading:
                # a TTL-expired job's disk read is still in flight: adopt
                # it instead of queueing a second read. Double-dispatch is
                # worse than wasteful — DiskKvPool pins are a set, so the
                # first completion's unpin strips eviction protection from
                # the second read mid-flight, and the collapsed _reading
                # entry breaks the max_inflight gate (found by dynmc, spec
                # prefetch_ttl; regression schedule committed)
                job.state = READING
            else:
                self._queue.append(h)
            self._jobs[h] = job
            self.stats["hinted_blocks"] += 1
        self._pump()

    # -- periodic (every engine inbox drain) ---------------------------------
    def tick(self) -> None:
        now = self._clock()
        if self._limited:
            self._budget_bytes = min(
                self._budget_burst,
                self._budget_bytes + (now - self._last_refill) * self._bps,
            )
        self._last_refill = now
        for h, job in list(self._jobs.items()):
            if job.state == PROMOTED:
                if now >= job.pin_deadline:
                    self.pool.unpin(h)
                    del self._jobs[h]
                    self._cancelled(1)
            elif now >= job.deadline:
                # QUEUED: drop (lazy queue removal). READING: drop the job;
                # the read result finds no job and is discarded.
                del self._jobs[h]
                self._cancelled(1)
        self._pump()

    def _cancelled(self, n: int) -> None:
        self.stats["cancelled"] += n
        self._m_cancelled.inc(n)

    # -- dispatch ------------------------------------------------------------
    def _pump(self) -> None:
        disk = self.tiered.disk
        obj = getattr(self.tiered, "obj", None)
        while self._queue:
            if self._limited and self._budget_bytes <= 0:
                break
            h = self._queue[0]
            job = self._jobs.get(h)
            if job is None or job.state != QUEUED:
                self._queue.popleft()  # cancelled / already moved on
                continue
            if h in self.tiered.host:
                self._queue.popleft()
                self._promote_from_host(job)
            elif disk is not None and h in disk:
                if len(self._reading) >= self.max_inflight:
                    break  # FIFO: wait for a slot rather than skip ahead
                self._queue.popleft()
                job.state = READING
                self._reading.add(h)
                self.stats["reading_peak"] = max(
                    self.stats["reading_peak"], len(self._reading))
                disk.pin(h)
                if not disk.read_block_async(h, self._on_disk_read):
                    self._reading.discard(h)
                    disk.unpin(h)
                    self._drop(job, "lost")
            elif obj is not None and h in obj:
                # G4-only: the shared object store serves promotions too
                # (a peer's demoted block, or our own after G3 churn) —
                # the fetch rides G4's writer thread like G3's file reads
                if len(self._reading) >= self.max_inflight:
                    break
                self._queue.popleft()
                job.state = READING
                self._reading.add(h)
                self.stats["reading_peak"] = max(
                    self.stats["reading_peak"], len(self._reading))
                obj.pin(h)
                if not obj.read_block_async(h, self._on_obj_read):
                    self._reading.discard(h)
                    obj.unpin(h)
                    self._drop(job, "lost")
            else:
                # not in any tier we promote from (evicted underneath us)
                self._queue.popleft()
                self._drop(job, "lost")

    def _drop(self, job: _Job, reason: str) -> None:
        self.stats[reason] += 1
        self._jobs.pop(job.h, None)

    # -- G3/G4 → G2 ----------------------------------------------------------
    def _on_disk_read(self, h: int, parent: Optional[int], k, v,
                      found: bool) -> None:
        """Disk writer thread: hand the bytes back to the step thread."""
        self.engine._inbox.put(("prefetch_disk", (h, parent, k, v, found)))

    def _on_obj_read(self, h: int, parent: Optional[int], k, v,
                     found: bool) -> None:
        """G4 writer thread: hand the bytes back to the step thread."""
        self.engine._inbox.put(("prefetch_obj", (h, parent, k, v, found)))

    def on_disk_read(self, h: int, parent: Optional[int], k, v,
                     found: bool) -> None:
        """Step thread (inbox op "prefetch_disk")."""
        self._on_lower_read(h, k, v, found, self.tiered.disk,
                            "bytes_promoted_g3")

    def on_obj_read(self, h: int, parent: Optional[int], k, v,
                    found: bool) -> None:
        """Step thread (inbox op "prefetch_obj")."""
        self._on_lower_read(h, k, v, found,
                            getattr(self.tiered, "obj", None),
                            "bytes_promoted_g4")

    def _on_lower_read(self, h: int, k, v, found: bool, pool,
                       hop_stat: str) -> None:
        self._reading.discard(h)
        if pool is not None:
            pool.unpin(h)
        job = self._jobs.get(h)
        if job is None or job.state != READING:
            self._pump()  # job cancelled/superseded while the read ran
            return
        if not found:
            self._drop(job, "lost")
            self._pump()
            return
        if k is not None:
            # one [L, PS, Hk, D] block — dense or quantized dict, exactly
            # as the lower tier stored it; the host tier absorbs either
            self.tiered.host.put_block(h, job.parent, k, v)
            nbytes = pair_nbytes(k, v)
        elif not self._sim_runner():
            # real engine, data-less read (corrupt/truncated block was
            # quarantined underneath us): nothing to promote
            self._drop(job, "lost")
            self._pump()
            return
        else:
            self.tiered.host.put([h], [job.parent], None, None)
            nbytes = int(self.sim_block_bytes * self._tier_byte_ratio())
        if self._limited:
            self._budget_bytes -= nbytes
        self.stats["bytes_promoted"] += nbytes
        self.stats[hop_stat] += nbytes
        job.state = QUEUED  # now host-resident: next stage
        self._promote_from_host(job)
        self._pump()

    def _sim_runner(self) -> bool:
        return not self.engine.runner.holds_kv

    def _tier_byte_ratio(self) -> float:
        """Stored-bytes scale for hash-only (sim) budget charges: 1.0 for
        dense tiers, the int8+scales ratio when the tier quantizes."""
        if not getattr(self.tiered.host, "quantize", False):
            return 1.0
        shape = self.engine.runner.kv_page_shape
        if shape:
            return quantized_ratio(int(shape[-1]))
        return quantized_ratio(128)

    # -- G2 → G1 -------------------------------------------------------------
    def _promote_from_host(self, job: _Job) -> None:
        from dynamo_tpu.engine.kv_pool import NoSpace
        from dynamo_tpu.engine.model_runner import kv_arrays_to_payload

        h = job.h
        try:
            k, v = self.tiered.host.get([h])
        except KeyError:
            return self._drop(job, "lost")
        if k is None and not self._sim_runner():
            return self._drop(job, "lost")
        try:
            page = self.pool.alloc(1)[0]
        except NoSpace:
            # device pool exhausted by live sequences: the synchronous
            # onboard handles this block at admission, when pages free up
            return self._drop(job, "no_space")
        if k is not None:
            payload = kv_arrays_to_payload(k, v)
            nbytes = k.nbytes + v.nbytes
        else:
            payload = {"sim": True, "data": True, "n_pages": 1}
            nbytes = self.sim_block_bytes
        self.engine.runner.import_pages([page], 0, payload)
        canonical = self.pool.register(page, h, job.parent)
        if canonical != page:
            # the synchronous path imported this block while we worked:
            # ours is a duplicate — return the page, keep theirs
            self.pool.release([page])
            return self._drop(job, "dup")
        self.pool.release([page])  # registered, ref 0 -> reusable cache
        self.pool.pin(h)
        now = self._clock()
        job.state = PROMOTED
        job.pin_deadline = now + self.pin_ttl_s
        if self._limited:
            self._budget_bytes -= nbytes
        self.stats["promoted"] += 1
        self.stats["bytes_promoted"] += nbytes
        self.stats["bytes_promoted_g2"] += nbytes
        self.stats["promote_latency_sum_s"] += now - job.t0
        self._m_bytes.inc(nbytes)
        if job.tp is not None:
            # promotions span several engine ticks; reconstruct the
            # interval retroactively under the route span that hinted it
            end_ns = time.time_ns()
            start_ns = end_ns - max(0, int((now - job.t0) * 1e9))
            tracing.record_span(
                "kv.prefetch.promote", start_ns, end_ns, parent=job.tp,
                attributes={"kv.block_hash": h, "kv.tier": "G2->G1",
                            "kv.bytes": nbytes})

    # -- accounting hooks ----------------------------------------------------
    def _on_claim(self, h: int) -> None:
        """PagePool claim hook: a pinned hash was claimed by match_prefix
        (the pool already dropped the pin)."""
        if self._jobs.pop(h, None) is not None:
            self.stats["hits"] += 1
            self._m_hits.inc()

    def note_sync_onboard(self, hashes: List[int]) -> None:
        """Engine's synchronous onboard path: any of these blocks still
        mid-promotion arrived LATE — cancel the job (the sync import wins;
        an in-flight duplicate resolves via register() dedup)."""
        for h in hashes:
            job = self._jobs.get(h)
            if job is None:
                continue
            if job.state == PROMOTED:
                # shouldn't happen (promoted blocks are device-resident and
                # excluded from sync-onboard candidates) — just unpin
                self.pool.unpin(h)
                del self._jobs[h]
            else:
                del self._jobs[h]
                self.stats["late"] += 1
                self._m_late.inc()

    # -- shutdown ------------------------------------------------------------
    def stop(self) -> None:
        """After the step thread has joined: release every pin."""
        for h, job in list(self._jobs.items()):
            if job.state == PROMOTED:
                self.pool.unpin(h)
        self._jobs.clear()
        self._queue.clear()
        self._reading.clear()

    @property
    def mean_promote_latency_s(self) -> float:
        n = self.stats["promoted"]
        return self.stats["promote_latency_sum_s"] / n if n else 0.0
