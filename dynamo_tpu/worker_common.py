"""Shared worker wiring: serve an InferenceEngine (real or mocker) with KV
event publishing, FPM publishing, and the kv_state recovery endpoint.

Mirrors the reference worker startup (components/src/dynamo/vllm/main.py:
engine boot → KV event publisher per dp_rank → register model → FPM relay →
serve_endpoint; SURVEY.md §3.2), collapsed into one helper both
`python -m dynamo_tpu.worker` and `python -m dynamo_tpu.mocker` use.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
from typing import Optional

from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.frontend.protocols import ModelCard
from dynamo_tpu.router.protocols import FPM_SUBJECT
from dynamo_tpu.router.publisher import KvEventPublisher
from dynamo_tpu.runtime.component import new_instance_id
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.tasks import spawn_tracked

log = logging.getLogger("dynamo_tpu.worker")

# cap per-pull payload (whole-KV msgpack messages; chunking is the P->D
# hardening item) — 64 blocks of a 3B model ~ 50MB bf16
MAX_HOST_FETCH_BLOCKS = 64


class ServedWorker:
    def __init__(self, runtime, engine, instance, publisher, close_hooks=None):
        self.runtime = runtime
        self.engine = engine
        self.instance = instance
        self.publisher = publisher
        self.digest_pub = None  # DigestPublisher when digests are on
        self._close_hooks = list(close_hooks or [])

    async def stop(self) -> None:
        self.engine.stop()
        if self.publisher is not None:
            await self.publisher.stop()
        for hook in self._close_hooks:
            try:
                r = hook()
                if hasattr(r, "__await__"):
                    await r
            except Exception:
                log.exception("worker close hook failed")


import weakref

# in-process engine registry: when prefill and decode engines share one
# process (colocated disagg — one TPU slice partitioned by role), the KV
# transfer stays entirely on device instead of a host-staged RPC
LOCAL_ENGINES: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


class DisaggDecodeAdapter:
    """Wraps the engine endpoint: requests carrying kv_transfer_src pull
    the parked KV pages from the prefill worker before admission. Same-
    process prefill engines (colocated disagg) transfer device-to-device;
    remote ones go over the request plane (host-staged DCN path)."""

    def __init__(self, engine: InferenceEngine, runtime: DistributedRuntime,
                 chunk_pages: int = 16):
        self.engine = engine
        self.runtime = runtime
        self.chunk_pages = chunk_pages  # 0 = monolithic single-message pull
        self._fetch_clients = {}

    async def _fetch(self, src, parent_ctx=None) -> Optional[dict]:
        local = LOCAL_ENGINES.get(src["instance_id"])
        # device path needs real runners on BOTH ends (mockers track KV at
        # hash level only and must never touch jax)
        if (
            local is not None
            and local is not self.engine
            and local.runner.holds_kv
            and self.engine.runner.holds_kv
        ):
            # device-resident transfer: gather on the prefill engine's step
            # thread, scatter on ours — no bytes touch the host
            return await local.export_parked_kv_device(src["request_id"])
        path = src["path"]
        client = self._fetch_clients.get(path)
        if client is None:
            client = self.runtime.client(path)
            await client.start()
            self._fetch_clients[path] = client
        client.router.update_instance(src["instance_id"], src["address"])
        # carry the trace across the P->D pull so the kv_fetch hop joins
        # the request's trace
        md = {}
        if parent_ctx is not None and parent_ctx.metadata.get("traceparent"):
            md["traceparent"] = parent_ctx.metadata["traceparent"]
        from dynamo_tpu.runtime.context import Context as _Ctx

        req = {"request_id": src["request_id"]}
        if self.chunk_pages:
            req["chunk_pages"] = self.chunk_pages
        chunks = []
        async for item in client.direct(
            req, src["instance_id"], _Ctx(metadata=md)
        ):
            if not self.chunk_pages:
                return item
            if item:
                chunks.append(item)
        if not chunks:
            return None
        if len(chunks) == 1 and "offset" not in chunks[0]:
            return chunks[0]  # server fell back to the monolithic path
        if not any(c.get("data") or c.get("device") for c in chunks):
            return None  # simulated / empty transfer: recompute locally
        # a truncated stream (prefill-side expiry/abort mid-transfer) must
        # trigger local recompute, never a half-imported KV cache
        total = int(chunks[0].get("total_pages") or 0)
        covered = sum(int(c.get("n_pages") or 0) for c in chunks)
        if total and covered < total:
            log.warning(
                "chunked KV pull truncated (%d/%d pages); recomputing",
                covered, total,
            )
            return None
        return {"chunks": chunks}

    async def generate(self, request, context):
        src = request.get("kv_transfer_src")
        if src is not None:
            try:
                payload = await self._fetch(src, parent_ctx=context)
            except Exception as e:
                log.warning("kv fetch from prefill worker failed: %s", e)
                payload = None
            request = dict(request)
            if payload is not None and (
                payload.get("data") or payload.get("device") or payload.get("chunks")
            ):
                request["kv_import"] = payload
            else:
                # transfer failed → recompute prefill locally (aggregated)
                ann = dict(request.get("annotations") or {})
                ann.pop("disagg", None)
                request["annotations"] = ann
            request.pop("kv_transfer_src", None)
        async for item in self.engine.generate(request, context):
            yield item


async def serve_worker(
    runtime: DistributedRuntime,
    engine: InferenceEngine,
    card: ModelCard,
    namespace: str = "dyn",
    component: str = "tpu-worker",
    endpoint: str = "generate",
    publish_kv_events: bool = True,
    publish_fpm: bool = True,
    digest_period_s: float = 2.0,  # fleet digest publish period (0 = off)
    dp_rank: int = 0,
    disagg_role: Optional[str] = None,  # None/"both" | "prefill" | "decode"
    disagg_chunk_pages: int = 16,  # P->D pull chunk size (0 = monolithic)
    device_weight: Optional[float] = None,  # capacity for device_aware
    #   routing (default: chips this worker's mesh spans)
    http_address: Optional[str] = None,  # this pod's HTTP frontend (direct-
    #   mode sidecar) for the ext-proc endpoint picker (DYN_HTTP_ADDRESS)
) -> ServedWorker:
    import os as _os

    instance_id = new_instance_id()
    LOCAL_ENGINES[instance_id] = engine  # colocated-disagg device transfer
    metadata = {"model_card": card.to_dict(), "dp_rank": dp_rank}
    http_address = http_address or _os.environ.get("DYN_HTTP_ADDRESS")
    if http_address:
        metadata["http_address"] = http_address
    if disagg_role:
        metadata["disagg_role"] = disagg_role
    # topology label for link-class routing: same kv_slice = ICI island,
    # different = DCN hop (engine slice_id wins; env for bare deploys)
    kv_slice = getattr(engine, "slice_id", None) \
        or _os.environ.get("DYN_KV_SLICE")
    if kv_slice:
        metadata["kv_slice"] = str(kv_slice)
    if device_weight is None:
        mesh = getattr(getattr(engine, "runner", None), "mesh_config", None)
        if mesh is not None:
            device_weight = float(mesh.n_devices)
    if device_weight is not None:
        metadata["device_weight"] = device_weight

    publisher = None
    if publish_kv_events:
        publisher = KvEventPublisher(
            runtime.event_publisher(), instance_id, dp_rank=dp_rank
        )
        await publisher.start()
        engine.on_kv_event(publisher.on_engine_events)
        metadata["kv_publisher"] = publisher.address
        await runtime.serve_endpoint(
            f"{namespace}/{component}/kv_state",
            publisher.dump_state,
            instance_id=instance_id,
        )

    if publish_fpm:
        import asyncio

        loop = asyncio.get_running_loop()
        pub = runtime.event_publisher()

        def on_fpm(m) -> None:  # called from the engine step thread
            payload = dataclasses.asdict(m)
            payload["worker"] = [instance_id, dp_rank]

            def _send() -> None:
                spawn_tracked(pub.publish(FPM_SUBJECT, payload), logger=log)

            loop.call_soon_threadsafe(_send)

        engine.on_fpm(on_fpm)
        metadata["fpm_publisher"] = pub.address

    # fleet digest plane (runtime/fleet_observer.py): compact periodic
    # summaries — phase histograms, queue depth, KV tier occupancy,
    # prefetch/compile counters — pushed over the event plane so the
    # frontend's FleetObserver / SLO engine and the planner never scrape.
    # Accumulation hooks run on the engine step thread (bucket increments
    # only); the publish task lives on the event loop.
    digest_pub = None
    if digest_period_s and digest_period_s > 0:
        from dynamo_tpu.runtime.fleet_observer import (
            DigestBuilder, DigestPublisher,
        )

        builder = DigestBuilder(instance_id, dp_rank)
        engine.on_fpm(builder.observe_fpm)
        if hasattr(engine, "on_phases"):
            engine.on_phases(builder.observe_phases)
        digest_pub = DigestPublisher(
            builder, runtime.event_publisher(), engine=engine,
            period_s=digest_period_s,
        )
        digest_pub.start()
        metadata["digest_publisher"] = digest_pub.address
        metadata["digest_period_s"] = digest_pub.period_s

    # disagg endpoints: prefill workers serve parked-KV pulls; decode
    # workers (and aggregated) accept transfer-carrying requests.
    # chunk_pages in the request selects the streamed export (bounded
    # message sizes, chunk reads interleaved with the prefill engine's
    # decode steps — disagg-serving.md bootstrap handoff); absent keeps
    # the single-message path (mockers, old callers).
    async def kv_fetch(request, context):
        req = request or {}
        chunk = int(req.get("chunk_pages") or 0)
        if chunk > 0 and hasattr(engine, "export_parked_kv_stream"):
            any_sent = False
            finished = False
            try:
                async for part in engine.export_parked_kv_stream(
                    req.get("request_id"), chunk
                ):
                    any_sent = True
                    yield part
                finished = True
                if not any_sent:
                    yield {}  # parked entry gone: caller recomputes
            finally:
                if not finished:
                    # puller died mid-stream (disconnect/cancel): release
                    # the parked pages now instead of pinning them for the
                    # full TTL (the monolithic path releases on first read)
                    try:
                        await engine.export_parked_kv(
                            req.get("request_id"), discard=True
                        )
                    except Exception:
                        # discard is best-effort cleanup after a dead
                        # puller; the parked TTL reclaims on failure
                        log.debug("parked-KV discard for %s failed "
                                  "(TTL will reclaim)",
                                  req.get("request_id"), exc_info=True)
            return
        yield await engine.export_parked_kv(
            req.get("request_id"), discard=bool(req.get("discard"))
        )

    await runtime.serve_endpoint(
        f"{namespace}/{component}/kv_fetch", kv_fetch, instance_id=instance_id
    )

    # RL admin surface (reference lib/rl: dyn://ns.comp.rl endpoints with
    # frontend read-only fan-in): pause/resume admission around weight
    # refreshes, orbax weight hot-swap, version reporting, dynamic LoRA
    # registration
    _served = {"inst": None}  # generate instance (set at the end of boot)

    async def rl_admin(request, context):
        req = request or {}
        op = req.get("op", "describe")
        if op == "pause":
            engine.paused = True
        elif op == "resume":
            engine.paused = False
        elif op == "load_adapter":
            # dynamic multi-LoRA: install an adapter into a free slot and
            # republish the model card — the frontend watcher registers
            # the new name as a servable model and routes ONLY to holders
            # (the late-adapter path of LoRA-filtered routing)
            name = req.get("name")
            runner = getattr(engine, "runner", None)
            if not name:
                yield {"error": "load_adapter needs 'name'"}
                return
            if runner is None or runner.lora is None:
                yield {"error": "worker built without --lora slots"}
                return
            if name in runner.adapter_names():
                # register_adapter would return the existing slot WITHOUT
                # touching its factors — reporting success while serving
                # stale weights. Make rollover explicit: new name, or
                # restart (slots are append-only by design).
                yield {"error": f"adapter {name!r} already registered; "
                                "weight rollover needs a new name"}
                return
            import asyncio as _aio

            import numpy as _np

            from dynamo_tpu.models import lora as lora_mod

            try:
                if req.get("peft"):
                    factors = await _aio.to_thread(
                        lora_mod.load_peft_adapter, req["peft"], runner.config
                    )
                else:  # dev adapters: random factors, seeded (an
                    # over-rank request hits the loud check below, same
                    # as the PEFT path — never a silent clamp)
                    factors = lora_mod.random_adapter(
                        runner.config, seed=int(req.get("seed") or 0),
                        scale=float(req.get("scale") or 2.0),
                        rank=int(req.get("rank") or runner.lora_rank),
                        targets=runner.lora_targets,
                    )
                # zero-pad up to the stacked tree's rank (same contract as
                # the boot path, worker._lora_kwargs): padded rows/cols
                # contribute nothing to A @ B. A HIGHER rank cannot fit
                # the fixed slot arrays — fail it loudly below instead of
                # truncating weights.
                for k, arr in list(factors.items()):
                    axis = -1 if k.endswith("_a") else -2
                    r = arr.shape[axis]
                    if r > runner.lora_rank:
                        raise ValueError(
                            f"adapter rank {r} exceeds the worker's "
                            f"--lora-rank {runner.lora_rank}"
                        )
                    if r < runner.lora_rank:
                        pad = [(0, 0)] * arr.ndim
                        pad[axis] = (0, runner.lora_rank - r)
                        factors[k] = _np.pad(arr, pad)
                slot = runner.register_adapter(name, factors)
            except Exception as e:
                yield {"error": f"adapter load failed: {e}"}
                return
            if name not in (card.adapters or []):
                card.adapters = list(card.adapters or []) + [name]
            if _served["inst"] is not None:
                await runtime.update_instance_metadata(
                    _served["inst"], {"model_card": card.to_dict()}
                )
            yield {"model": card.name, "adapter": name, "slot": slot,
                   "adapters": list(card.adapters), "instance": instance_id}
            return
        elif op == "update_weights":
            path = req.get("orbax")
            if not path:
                yield {"error": "update_weights needs 'orbax': <snapshot dir>"}
                return
            try:
                version = await engine.update_weights(path)
            except Exception as e:
                yield {"error": f"weight reload failed: {e}"}
                return
            yield {
                "model": card.name, "paused": bool(engine.paused),
                "weights_version": version, "instance": instance_id,
            }
            return
        elif op != "describe":
            yield {"error": f"unknown rl op {op!r}"}
            return
        yield {
            "model": card.name,
            "paused": bool(getattr(engine, "paused", False)),
            "weights_version": int(getattr(engine, "weights_version", 0)),
            "instance": instance_id,
        }

    if hasattr(engine, "update_weights"):
        await runtime.serve_endpoint(
            f"{namespace}/{component}/rl", rl_admin, instance_id=instance_id
        )

    # cross-worker KVBM onboarding (reference kvbm-engine onboarding
    # sessions): peers pull lower-tier blocks from this worker, and this
    # worker pulls from peers when the router's hint names one
    async def kv_host_fetch(request, context):
        hashes = [int(h) for h in (request or {}).get("hashes") or []]
        return await engine.export_host_blocks(hashes[:MAX_HOST_FETCH_BLOCKS])

    await runtime.serve_endpoint(
        f"{namespace}/{component}/kv_host_fetch", kv_host_fetch,
        instance_id=instance_id,
    )

    # predictive prefetch plane (kvbm/prefetch.py): the router announces
    # what the inbound request will need BEFORE dispatching it; the
    # engine's PrefetchManager promotes those blocks up the KVBM ladder
    # while the request is still queueing. Advertised via metadata so
    # routers skip workers without a manager.
    if getattr(engine, "prefetch", None) is not None:
        metadata["kv_prefetch"] = True
        # counters must live in the runtime's registry or the status
        # port's /metrics never sees them
        engine.prefetch.bind_metrics(runtime.metrics.child(dynamo_namespace=namespace))

    # compile-cache observability: per step-function family (forward /
    # decode_loop / mixed / ragged), compiled-variant count and cumulative
    # trace+compile seconds. Refreshed from the step thread's FPM hook —
    # compiles only happen during steps, so the gauges are never stale
    # when someone scrapes after a step completed. The ragged mixed path's
    # cardinality collapse (variants <= |T buckets|) is read off these.
    _runner = getattr(engine, "runner", None)
    if _runner is not None and _runner.compile_stats():
        _cm = runtime.metrics.child(dynamo_namespace=namespace)

        def _update_compile_gauges(_m=None) -> None:
            for fam, st in _runner.compile_stats().items():
                _cm.gauge(
                    "compile_variants",
                    "compiled XLA variants per step-function family",
                    family=fam,
                ).set(st["variants"])
                _cm.gauge(
                    "compile_seconds_total",
                    "cumulative trace+compile wall seconds per family",
                    family=fam,
                ).set(st["compile_s"])

        engine.on_fpm(_update_compile_gauges)
        _update_compile_gauges()

    # the step loop's run-ahead -> /metrics: iterations by how they were
    # enqueued, "ahead" of the read-back of the one before or the reason
    # not (IterationRecord.drain; docs/observability.md "Run-ahead")
    if hasattr(engine, "run_ahead_totals"):
        _rm = runtime.metrics.child(dynamo_namespace=namespace)
        _ahead_sent: dict = {}

        def _update_run_ahead(_m=None) -> None:
            for outcome, n in list(engine.run_ahead_totals.items()):
                _rm.counter(
                    "engine_run_ahead_total",
                    "engine iterations by how they were enqueued: ahead of "
                    "the read-back of the iteration before, or why not",
                    outcome=outcome,
                ).inc(n - _ahead_sent.get(outcome, 0))
                _ahead_sent[outcome] = n

        engine.on_fpm(_update_run_ahead)

    # routed experts -> /metrics: the engine's expert-load counters
    # (IterationRecord.moe_*; docs/observability.md "Routed experts") as
    # two gauges of the newest iteration and two running totals. Only a
    # worker whose step programs hand out the picks has the series.
    if _runner is not None and _runner.routed:
        _mm = runtime.metrics.child(dynamo_namespace=namespace)
        _moe_sent = {"slots": 0, "held": 0.0}

        def _update_moe_gauges(_m=None) -> None:
            t = engine.moe_totals
            _mm.gauge(
                "moe_experts_hit",
                "experts picked at least once in a forward, mean over "
                "expert layers and the last iteration's forwards",
            ).set(t["experts_hit"])
            _mm.gauge(
                "moe_load_max_share",
                "share of a forward's tokens on the fullest expert, mean "
                "over expert layers and the last iteration's forwards",
            ).set(t["load_max_share"])
            _mm.counter(
                "moe_token_slots_total",
                "routed token-slots served (real tokens x experts a token)",
            ).inc(t["token_slots_total"] - _moe_sent["slots"])
            _moe_sent["slots"] = t["token_slots_total"]
            _mm.counter(
                "moe_held_slots_total",
                "routed token-slots that fell to the experts this worker "
                "holds, mean over expert layers (all of them unless it "
                "holds a share)",
            ).inc(t["held_slots_total"] - _moe_sent["held"])
            _moe_sent["held"] = t["held_slots_total"]

        engine.on_fpm(_update_moe_gauges)
        _update_moe_gauges()

    # the side cache -> /metrics: what a sequence keeps beside its KV pages
    # (engine/side_cache.py `gauges`; docs/observability.md "A sequence's
    # state slot", "A sequence's two page tables"). Only a worker whose
    # model has one has the series.
    _side = getattr(engine, "side", None)
    if _side is not None:
        _sm = runtime.metrics.child(dynamo_namespace=namespace)

        def _update_side_gauges(_m=None) -> None:
            for name, doc, value in _side.gauges():
                _sm.gauge(name, doc).set(value)

        engine.on_fpm(_update_side_gauges)
        _update_side_gauges()

    # latency spine -> /metrics: per-finished-request phase durations as
    # histograms labeled by phase (queue_wait/ttft/kv_onboard/...; ITL
    # samples fold into one phase="itl" histogram). Fired from the engine
    # step thread via on_phases; histogram observe is lock-cheap.
    if hasattr(engine, "on_phases"):
        _pm = runtime.metrics.child(dynamo_namespace=namespace)

        def _observe_phases(phases: dict) -> None:
            for key, val in phases.items():
                if key == "itl_s" and isinstance(val, list):
                    h = _pm.histogram(
                        "request_phase_seconds",
                        "per-request latency spine phase durations",
                        phase="itl")
                    for s in val:
                        h.observe(float(s))
                elif not isinstance(val, (int, float)):
                    continue
                elif key.endswith("_s"):
                    _pm.histogram(
                        "request_phase_seconds",
                        "per-request latency spine phase durations",
                        phase=key.removesuffix("_s"),
                    ).observe(float(val))
                else:
                    # the spine's counts (preemptions, prefill_iters,
                    # migration_attempts) are no durations: summed here,
                    # never observed into a seconds histogram
                    _pm.counter(
                        "request_phase_count",
                        "per-request latency spine counts, summed",
                        phase=key,
                    ).inc(float(val))

        engine.on_phases(_observe_phases)

    # flight recorder: fired-anomaly counter and the step clock's
    # engine_host_seconds_total{phase, exposed} (docs/observability.md
    # "Run-ahead") onto the shared registry, and
    # advertise the recorder via metadata so tooling knows /debug/timeline
    # is live on this worker's status port
    _rec = getattr(engine, "recorder", None)
    if _rec is not None and getattr(_rec, "enabled", False):
        _rec.bind_metrics(
            runtime.metrics.child(dynamo_namespace=namespace))
        metadata["flight_recorder"] = True

    async def kv_prefetch(request, context):
        hint = (request or {}).get("kv_prefetch") or {}
        ok = False
        if getattr(engine, "prefetch", None) is not None and hint:
            ok = await engine.prefetch_hint_async(hint)
        yield {"ok": bool(ok)}

    await runtime.serve_endpoint(
        f"{namespace}/{component}/kv_prefetch", kv_prefetch,
        instance_id=instance_id,
    )

    _fetch_clients: dict = {}

    async def _remote_kv_fetch(hint):
        from dynamo_tpu.runtime import tracing

        path = hint["path"]
        client = _fetch_clients.get(path)
        if client is None:
            client = runtime.client(path)
            # cache before any await that can raise: a failed first pull
            # must not leak a client (and its discovery-watch task) per
            # request; direct() surfaces cannot_connect on its own
            _fetch_clients[path] = client
            await client.start()
        # cross-worker onboarding pull as a traced hop: the router stamped
        # the route span's traceparent into the hint, so this transfer
        # joins the request's trace with tier + size attribution
        with tracing.span(
            "kv.peer_pull", parent=hint.get("traceparent"), kind=3,
            attributes={
                "kv.n_blocks": len(hint.get("hashes") or []),
                "kv.peer_instance": int(hint["instance"]),
            },
        ):
            # first pull after client creation races the discovery watch:
            # give the target instance a moment to appear instead of
            # failing into the engine's 30s peer backoff
            deadline = asyncio.get_running_loop().time() + 2.0
            while (int(hint["instance"]) not in client.instances
                   and asyncio.get_running_loop().time() < deadline):
                await asyncio.sleep(0.05)
            req = {"hashes":
                   [int(h) for h in hint["hashes"][:MAX_HOST_FETCH_BLOCKS]]}
            async for item in client.direct(req, int(hint["instance"])):
                return item
            return None

    engine.remote_kv_fetch = _remote_kv_fetch

    async def _close_fetch_clients():
        for c in _fetch_clients.values():
            await c.close()

    close_hooks = [_close_fetch_clients]
    if digest_pub is not None:
        # final flush on stop: the last partial window still reaches the
        # observer (the chaos suite's mid-window death is the case where
        # it does NOT flush — SIGKILL — and the observer must cope)
        close_hooks.append(digest_pub.stop)
    handler = DisaggDecodeAdapter(engine, runtime, chunk_pages=disagg_chunk_pages)

    engine.start()
    inst = await runtime.serve_endpoint(
        f"{namespace}/{component}/{endpoint}",
        handler,
        metadata=metadata,
        instance_id=instance_id,
    )
    _served["inst"] = inst  # rl load_adapter republishes this card
    log.info("worker %x serving %s (role=%s)", instance_id, card.name, disagg_role or "both")
    served = ServedWorker(runtime, engine, inst, publisher, close_hooks=close_hooks)
    served.digest_pub = digest_pub
    return served
