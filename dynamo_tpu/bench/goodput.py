"""SLO goodput benchmark against the real serving stack.

`python -m dynamo_tpu.bench.goodput --model llama-3.2-3b --rps 4 ...`

Boots the full in-process stack — worker engine(s) (real ModelRunner on
the local accelerator, or the calibrated SimRunner mocker), the discovery
plane, the TCP request plane, and the frontend pipeline (Migration →
Backend detok → PrefillRouter → KV router) — then fires a Poisson trace at
it and reports **goodput**: output tokens/s over requests that met BOTH
the TTFT and ITL SLOs. This is BASELINE.md's metric (reference
docs/benchmarks/benchmarking.md:449), not raw decode throughput.

Modes:
- aggregated (default): N workers, each prefill+decode
- --disagg: decode worker(s) plus a prefill worker pool (the reference's
  P/D split)
Real engines are one-device replicas: worker i runs on device i, and more
workers than devices is an error.
- --mocker: SimRunner workers — measures the serving plane itself
  (frontend+router+transport ceiling, SURVEY §2.9 hardening item)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
from dataclasses import dataclass
from typing import Any, List, Optional

from dynamo_tpu.bench.loadgen import (
    GoodputReport,
    aggregate_migration,
    aggregate_phases,
    compute_goodput,
    compute_scenario_matrix,
    generate_burst_trace,
    generate_scenarios,
    generate_trace,
    load_trace,
    run_sessions_against_engine,
    run_trace_against_engine,
)

log = logging.getLogger("dynamo_tpu.bench")


@dataclass
class Stack:
    """A booted serving stack: frontend chain + workers, all in-process
    but talking over the real discovery/request/event planes."""

    frontend_runtime: Any
    worker_runtimes: List[Any]
    workers: List[Any]
    watcher: Any
    entry: Any  # ModelEntry: .chain is the frontend pipeline
    broker: Any = None  # MiniNatsServer when --request-plane nats booted one
    nats_env_prev: Any = False  # False = untouched; None/str = prior value
    fleet: Any = None  # FleetObserver over the workers' digest publishers
    slo: Any = None  # SloEngine bound to `fleet` (--digest-period > 0)

    async def generate(self, request, context):
        async for item in self.entry.chain.generate(request, context):
            yield item

    async def close(self) -> None:
        if self.fleet is not None:
            await self.fleet.stop()
        await self.watcher.stop()
        await self.frontend_runtime.shutdown()
        for w in self.workers:
            try:
                await w.stop()
            except Exception:
                # teardown is best-effort: a worker that died mid-bench
                # must not mask the runtimes' shutdown below
                log.debug("worker stop failed during teardown", exc_info=True)
        for rt in self.worker_runtimes:
            try:
                await rt.shutdown(drain_timeout=2)
            except Exception:
                log.debug("runtime shutdown failed during teardown",
                          exc_info=True)
        if self.broker is not None:
            await self.broker.stop()
        if self.nats_env_prev is not False:
            import os as _os

            # restore DYN_NATS_URL: leaving it pointing at the dead
            # in-process broker would break the next boot in this process
            if self.nats_env_prev is None:
                _os.environ.pop("DYN_NATS_URL", None)
            else:
                _os.environ["DYN_NATS_URL"] = self.nats_env_prev


def _make_engine(args, mocker: bool, replica: int = 0):
    """One worker engine. Real runners are single-device replicas: replica
    i takes device i, so `--workers 4` on a four-chip host is four chips
    and not four runners stacked on chip 0."""
    from dynamo_tpu.engine.engine import InferenceEngine

    if mocker:
        from dynamo_tpu.mocker.sim import SimRunner, SimTiming

        runner = SimRunner(
            num_pages=args.num_pages,
            page_size=args.page_size,
            max_pages_per_seq=args.max_pages_per_seq,
            timing=SimTiming(
                speed=args.sim_speed,
                prefill_cost=getattr(args, "sim_prefill_cost", "ragged"),
            ),
            spec_accept_rate=getattr(args, "spec_accept_rate", None),
        )
    else:
        import jax

        from dynamo_tpu.engine.model_runner import ModelRunner
        from dynamo_tpu.models.config import get_config

        devices = jax.devices()
        if replica >= len(devices):
            raise ValueError(
                f"replica {replica} needs its own device; JAX reports "
                f"{len(devices)}"
            )
        runner = ModelRunner(
            get_config(args.model),
            devices=devices[replica : replica + 1],
            num_pages=args.num_pages,
            page_size=args.page_size,
            max_pages_per_seq=args.max_pages_per_seq,
            decode_buckets=tuple(args.decode_buckets),
            prefill_buckets=tuple(args.prefill_buckets),
            seed=0,
            quantize=args.quantize,
        )
    return InferenceEngine(
        runner,
        max_batch=args.max_batch,
        chunk_size=args.chunk_size,
        mixed_prefill_tokens=args.mixed_prefill_tokens,
        mixed_prefill_seqs=getattr(args, "mixed_prefill_seqs", 8),
        mixed_min_chunk=getattr(args, "mixed_min_chunk", 16),
        host_kv_blocks=args.host_kv_blocks,
        disk_kv_blocks=getattr(args, "disk_kv_blocks", 0),
        prefetch=getattr(args, "prefetch", False),
        prefetch_max_inflight=getattr(args, "prefetch_max_inflight", 4),
        prefetch_bandwidth_mbps=getattr(args, "prefetch_bandwidth_mbps", 0.0),
        spec_ngram=getattr(args, "spec_ngram", False),
        spec_k=getattr(args, "spec_k", 4),
        spec_max_tokens=getattr(args, "spec_max_tokens", 0),
        enable_prefix_cache=not getattr(args, "no_prefix_cache", False),
    )


async def boot_stack(args, mocker: bool = False, disagg: bool = False) -> Stack:
    from dynamo_tpu.frontend.protocols import ModelCard
    from dynamo_tpu.frontend.service import ModelManager, ModelWatcher
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    realm = f"goodput-{id(args):x}"
    card = ModelCard(
        name=args.model, tokenizer="byte",
        context_length=args.page_size * args.max_pages_per_seq,
        kv_block_size=args.page_size,
    )
    worker_runtimes, workers = [], []
    # --request-plane nats: RPC rides broker subjects; boot an in-process
    # broker when none is configured, so the SLO bench measures the NATS
    # plane standalone (addresses are self-describing — the frontend
    # needs no flag)
    plane = getattr(args, "request_plane", None) or "tcp"
    broker = None
    nats_env_prev: Any = False
    import os as _os

    if plane == "nats" and not _os.environ.get("DYN_NATS_URL"):
        from dynamo_tpu.runtime.nats_plane import MiniNatsServer

        broker = MiniNatsServer()
        nats_env_prev = _os.environ.get("DYN_NATS_URL")
        _os.environ["DYN_NATS_URL"] = await broker.start()

    try:
        return await _boot_rest(
            args, mocker, disagg, plane, realm, card, worker_runtimes,
            workers, broker, nats_env_prev,
        )
    except BaseException:
        # a failed boot must not leak the in-process broker or leave
        # DYN_NATS_URL pointing at it — a retry would dial a dead port
        if broker is not None:
            await broker.stop()
        if nats_env_prev is not False:
            if nats_env_prev is None:
                _os.environ.pop("DYN_NATS_URL", None)
            else:
                _os.environ["DYN_NATS_URL"] = nats_env_prev
        raise


async def _boot_rest(args, mocker, disagg, plane, realm, card,
                     worker_runtimes, workers, broker, nats_env_prev) -> Stack:
    from dynamo_tpu.frontend.service import ModelManager, ModelWatcher
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.worker_common import serve_worker

    async def add_worker(role: Optional[str], component: str):
        rt = DistributedRuntime(
            discovery=MemDiscovery(realm=realm), event_transport="inproc",
            request_plane=plane,
        )
        engine = _make_engine(args, mocker, replica=len(workers))
        w = await serve_worker(
            rt, engine, card, component=component, disagg_role=role,
            digest_period_s=getattr(args, "digest_period", 0.0),
        )
        worker_runtimes.append(rt)
        workers.append(w)

    if disagg:
        for _ in range(args.workers):
            await add_worker("decode", "decode")
        for _ in range(args.prefill_workers):
            await add_worker("prefill", "prefill")
    else:
        for _ in range(args.workers):
            await add_worker(None, "worker")

    frt = DistributedRuntime(
        discovery=MemDiscovery(realm=realm), event_transport="inproc"
    )
    manager = ModelManager()
    watcher = ModelWatcher(
        frt, manager, router_mode=args.router_mode,
        disagg_min_prefill_tokens=args.disagg_min_prefill_tokens,
    )
    await watcher.start()
    await watcher.wait_for_model(timeout=60)
    entry = manager.get(args.model)
    # wait for every instance to be routable — timing a half-booted stack
    # would report a plausible-looking goodput of 0 instead of failing
    for _ in range(200):
        ready = len(entry.instance_ids) >= args.workers
        if disagg:
            # prefill_router.active requires the prefill CLIENT's own
            # discovery watch to have seen the instances, not just the
            # watcher's registry — route-ready is what matters
            ready = (ready
                     and len(entry.prefill_instance_ids) >= args.prefill_workers
                     and entry.prefill_router is not None
                     and entry.prefill_router.active)
        if ready:
            break
        await asyncio.sleep(0.05)
    else:
        raise TimeoutError(
            f"stack not routable: {len(entry.instance_ids)}/{args.workers} "
            f"workers (+{len(entry.prefill_instance_ids)} prefill)"
        )
    fleet = slo_engine = None
    if getattr(args, "digest_period", 0.0) > 0:
        # fleet observability ride-along: the inproc event bus is
        # process-global, so the frontend runtime's subscriber reaches the
        # workers' digest publishers directly
        from dynamo_tpu.planner.slo import SloEngine, parse_slo_config
        from dynamo_tpu.runtime.event_plane import FLEET_DIGEST_SUBJECT
        from dynamo_tpu.runtime.fleet_observer import FleetObserver

        fleet = FleetObserver(
            frt.event_subscriber([FLEET_DIGEST_SUBJECT]),
            window_s=getattr(args, "digest_window", 60.0),
        )
        for w in workers:
            addr = (w.instance.metadata or {}).get("digest_publisher")
            if addr:
                fleet.connect_publisher(addr)
        await fleet.start()
        spec = getattr(args, "slo", None) or (
            f"ttft:p95<{args.ttft_slo:g},itl:p95<{args.itl_slo:g}")
        slo_engine = SloEngine(fleet, parse_slo_config(spec))
    return Stack(frt, worker_runtimes, workers, watcher, entry,
                 broker=broker, nats_env_prev=nats_env_prev,
                 fleet=fleet, slo=slo_engine)


async def run_goodput(args) -> GoodputReport:
    scenarios = None
    if getattr(args, "scenarios", None):
        scenarios = generate_scenarios(
            args.scenarios, n_sessions=args.n_requests, rps=args.rps,
            seed=args.seed)
        trace = []
    elif args.trace:
        trace = load_trace(args.trace)
    elif getattr(args, "burst_size", 0) > 0:
        trace = generate_burst_trace(
            args.n_requests, burst_size=args.burst_size,
            burst_interval_s=args.burst_interval,
            isl_mean=args.isl, osl_mean=args.osl,
            prefix_groups=args.prefix_groups, seed=args.seed,
        )
    else:
        trace = generate_trace(
            args.n_requests, rps=args.rps, isl_mean=args.isl, osl_mean=args.osl,
            prefix_groups=args.prefix_groups, seed=args.seed,
        )
    stack = await boot_stack(args, mocker=args.mocker, disagg=args.disagg)
    try:
        if not args.mocker:
            await _warmup(stack, args)
        if scenarios is not None:
            results, duration = await run_sessions_against_engine(
                scenarios, stack.generate, time_scale=args.time_scale,
                seed=args.seed,
            )
        else:
            results, duration = await run_trace_against_engine(
                trace, stack.generate, time_scale=args.time_scale,
                seed=args.seed,
            )
        # aggregate worker-side prefetch counters before teardown so a
        # --prefetch A/B can tell "hints landed" from "nothing fired"
        prefetch_stats = None
        if getattr(args, "prefetch", False):
            prefetch_stats = {}
            for w in stack.workers:
                pf = getattr(w.engine, "prefetch", None)
                if pf is None:
                    continue
                for k, v in pf.stats.items():
                    prefetch_stats[k] = prefetch_stats.get(k, 0) + v
        # compile-cache observability: per step-function family, summed
        # across workers — the ragged path's acceptance criterion (mixed
        # variants <= |T buckets|) is checked off this artifact
        compile_stats = {}
        sim_stats = {}
        spec_stats = {}
        worker_devices = []  # per real worker: the devices its mesh holds
        for w in stack.workers:
            runner = getattr(w.engine, "runner", None)
            if hasattr(runner, "mesh"):
                worker_devices.append(
                    [f"{d.platform}:{d.id}" for d in runner.mesh.devices.flat]
                )
            if hasattr(runner, "compile_stats"):
                for fam, st in runner.compile_stats().items():
                    agg = compile_stats.setdefault(
                        fam, {"variants": 0, "compile_s": 0.0, "calls": 0}
                    )
                    for k in agg:
                        agg[k] += st.get(k, 0)
            for k, v in getattr(runner, "stats", {}).items():
                sim_stats[k] = sim_stats.get(k, 0) + v
            for k, v in getattr(w.engine, "spec_stats", {}).items():
                spec_stats[k] = spec_stats.get(k, 0) + v
        # fleet digest ride-along: flush each worker's tail window, then
        # snapshot the observer + SLO attainment before teardown
        fleet_view = slo_view = None
        if stack.fleet is not None:
            for w in stack.workers:
                if w.digest_pub is not None:
                    await w.digest_pub.publish_once()
            await asyncio.sleep(0.05)  # inproc bus delivery
            fleet_view = stack.fleet.fleet()
            slo_view = stack.slo.evaluate()
    finally:
        await stack.close()
    report = compute_goodput(
        results, duration, ttft_slo_s=args.ttft_slo, itl_slo_s=args.itl_slo
    )
    if prefetch_stats is not None:
        report.extras["prefetch"] = {
            k: round(v, 6) for k, v in prefetch_stats.items()
        }
    if compile_stats:
        report.extras["compile"] = {
            fam: {"variants": st["variants"],
                  "compile_s": round(st["compile_s"], 4),
                  "calls": st["calls"]}
            for fam, st in compile_stats.items()
        }
    if sim_stats:
        report.extras["sim"] = sim_stats
    if worker_devices:
        report.extras["worker_devices"] = worker_devices
    if spec_stats.get("verify_iters"):
        report.extras["spec"] = {
            **spec_stats,
            "accept_rate": round(
                spec_stats["accepted"] / max(1, spec_stats["drafted"]), 4
            ),
            "tokens_per_step": round(
                spec_stats["spec_emitted"]
                / max(1, spec_stats["verify_rows"]), 4
            ),
        }
    if fleet_view is not None:
        report.extras["fleet"] = {
            "n_workers": fleet_view["n_workers"],
            "received": fleet_view["received"],
            "dropped_stale": fleet_view["dropped_stale"],
            "phases": fleet_view["fleet"]["phases"],
            "workers": {
                k: {"requests": row["counters"]["requests"],
                    "phases": row["phases"]}
                for k, row in fleet_view["workers"].items()
            },
        }
    if slo_view is not None:
        report.extras["slo"] = {
            "state": slo_view["state"],
            "targets": {
                name: {"state": s["state"], "fast": s["fast"],
                       "slow": s["slow"]}
                for name, s in slo_view["fleet"].items()
            },
        }
    if scenarios is not None:
        # the scenario goodput matrix: per-scenario goodput, phase
        # aggregates, and the turn-split TTFT (tree-reuse legibility)
        report.extras["scenarios"] = compute_scenario_matrix(
            results, duration, args.ttft_slo, args.itl_slo)
        tree_stats = {}
        for w in stack.workers:
            sched = getattr(w.engine, "scheduler", None)
            pool = getattr(w.engine, "pool", None)
            for k, v in (("reused_prefix_tokens",
                          getattr(sched, "reused_prefix_tokens", 0)),
                         ("prompt_tokens", getattr(sched, "prompt_tokens_total", 0)),
                         ("hit_blocks", getattr(pool, "match_hit_blocks", 0)),
                         ("forks", getattr(pool, "forks", 0))):
                tree_stats[k] = tree_stats.get(k, 0) + int(v or 0)
        if tree_stats.get("prompt_tokens"):
            tree_stats["hit_rate"] = round(
                tree_stats["reused_prefix_tokens"]
                / tree_stats["prompt_tokens"], 4)
        report.extras["tree"] = tree_stats
    # migration counters (Migration's phase-spine stamps): how many
    # requests migrated, how many retries they spent, and what fraction
    # finished — the robustness headline under worker churn
    mig = aggregate_migration(results)
    if mig:
        report.extras["migration"] = mig
    # per-request latency spine: queue_wait / TTFT / ITL / kv_onboard
    # breakdowns from the phase stamps that rode each final item
    phase_agg = aggregate_phases(results)
    if phase_agg:
        report.extras["phases"] = {
            key: {"n": st["n"],
                  "p50_s": round(st["p50_s"], 6),
                  "p95_s": round(st["p95_s"], 6)}
            for key, st in phase_agg.items()
        }
    return report


async def _warmup(stack, args) -> None:
    """Compile outside the measured window (first XLA compile is minutes on
    TPU): per worker instance, one prefill per prefill bucket, plus a
    concurrent burst sized to the largest decode bucket so the big decode
    shapes compile too. Intermediate decode buckets hit during the run
    still compile lazily — shrink --decode-buckets if that matters."""
    from dynamo_tpu.runtime.context import Context

    max_ctx = args.page_size * args.max_pages_per_seq

    async def one(target, isl, max_tokens=4):
        req = {
            "token_ids": list(range(300, 300 + isl)),
            "sampling": {"temperature": 0.0},
            "stop": {"max_tokens": max_tokens, "stop_ids": [],
                     "ignore_eos": True},
        }
        ctx = Context(metadata={"target_instance": target} if target else {})
        try:
            async for item in stack.generate(req, ctx):
                if item.get("finish_reason"):
                    break
        except Exception as e:
            log.warning("warmup request failed: %s", e)

    instances = sorted(stack.entry.instance_ids)
    for iid in instances:
        for pb in args.prefill_buckets:
            isl = max(8, min(pb, max_ctx - 8))
            await one(iid, isl)
    burst = max(args.decode_buckets)
    for iid in instances:
        await asyncio.gather(*[one(iid, 8) for _ in range(burst)])
    if stack.entry.prefill_instance_ids:
        # disagg: long prompts route through the prefill pool via the chain
        for pb in args.prefill_buckets:
            isl = max(args.disagg_min_prefill_tokens, min(pb, max_ctx - 8))
            for _ in range(len(stack.entry.prefill_instance_ids)):
                await one(None, isl)


def parse_args(argv=None):
    p = argparse.ArgumentParser("dynamo_tpu.bench.goodput")
    p.add_argument("--model", default="llama-3.2-3b")
    p.add_argument("--mocker", action="store_true",
                   help="SimRunner workers: measures the serving-plane ceiling")
    p.add_argument("--sim-speed", type=float, default=1.0)
    p.add_argument("--sim-prefill-cost", default="ragged",
                   choices=["ragged", "padded"],
                   help="mocker packed-prefill cost model: 'ragged' bills "
                        "sum(chunk_tokens) like the flat-token dispatch, "
                        "'padded' bills N_bucket*S_bucket like the legacy "
                        "[N, S] device path (for honest pre-ragged A/Bs)")
    p.add_argument("--disagg", action="store_true")
    p.add_argument("--workers", type=int, default=1)
    p.add_argument("--prefill-workers", type=int, default=1)
    p.add_argument("--request-plane", default=None, choices=[None, "tcp", "nats"],
                   help="worker RPC transport (nats boots an in-process "
                        "broker when DYN_NATS_URL is unset)")
    p.add_argument("--router-mode", default="kv",
                   choices=["round_robin", "random", "kv"])
    p.add_argument("--disagg-min-prefill-tokens", type=int, default=256)
    p.add_argument("--quantize", default=None, choices=[None, "int8", "fp8"])
    # engine shape
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--page-size", type=int, default=64)
    p.add_argument("--max-pages-per-seq", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--chunk-size", type=int, default=512)
    p.add_argument("--mixed-prefill-tokens", type=int, default=256,
                   help="per-iteration prefill token POOL when co-scheduled "
                        "with decode, fair-shared across packed chunks "
                        "(0 = strict prefill-first alternation)")
    p.add_argument("--mixed-prefill-seqs", type=int, default=8,
                   help="max distinct prefills packed per iteration "
                        "(1 = legacy single-chunk MixedPlan)")
    p.add_argument("--mixed-min-chunk", type=int, default=16,
                   help="fair-share floor per packed prefill sequence")
    p.add_argument("--spec-ngram", action="store_true",
                   help="speculative decoding: n-gram drafts verified as "
                        "ragged rows of the mixed dispatch")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft length K per speculating sequence")
    p.add_argument("--spec-max-tokens", type=int, default=0,
                   help="per-iteration drafted-token cap (0 = leftover "
                        "mixed prefill budget)")
    p.add_argument("--spec-accept-rate", type=float, default=None,
                   help="mocker-only oracle drafter accept rate (A/B knob; "
                        "overrides n-gram lookup)")
    p.add_argument("--no-prefix-cache", action="store_true",
                   help="disable block-hash prefix/tree KV reuse (the "
                        "cold side of the session-tree A/B)")
    p.add_argument("--host-kv-blocks", type=int, default=0)
    p.add_argument("--disk-kv-blocks", type=int, default=0)
    p.add_argument("--prefetch", action="store_true",
                   help="router-hinted predictive KV promotion (needs "
                        "--host-kv-blocks > 0); the off/on pair is the "
                        "prefetch A/B")
    p.add_argument("--prefetch-max-inflight", type=int, default=4)
    p.add_argument("--prefetch-bandwidth-mbps", type=float, default=0.0)
    p.add_argument("--decode-buckets", type=int, nargs="+", default=[8, 16, 32])
    p.add_argument("--prefill-buckets", type=int, nargs="+",
                   default=[128, 256, 512])
    # workload
    p.add_argument("--trace", default=None, help="JSONL trace file (else synthetic)")
    p.add_argument("--scenarios", nargs="+", default=None,
                   choices=["agentic", "rag", "json", "burst"],
                   help="scenario goodput matrix: run these session "
                        "scenarios (--n-requests sessions EACH) instead of "
                        "a flat trace; the report gains extras.scenarios "
                        "(per-scenario goodput + turn-split TTFT) and "
                        "extras.tree (prefix-tree reuse counters)")
    p.add_argument("--n-requests", type=int, default=64)
    p.add_argument("--rps", type=float, default=4.0)
    p.add_argument("--burst-size", type=int, default=0,
                   help="bursty arrivals: cohorts of this many simultaneous "
                        "requests instead of a poisson trace (0 = off)")
    p.add_argument("--burst-interval", type=float, default=2.0,
                   help="seconds between burst cohorts")
    p.add_argument("--isl", type=int, default=256)
    p.add_argument("--osl", type=int, default=64)
    p.add_argument("--prefix-groups", type=int, default=0)
    p.add_argument("--time-scale", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    # SLOs (reference benchmarking.md interactive defaults)
    p.add_argument("--ttft-slo", type=float, default=2.0, help="seconds")
    p.add_argument("--itl-slo", type=float, default=0.05, help="seconds")
    # fleet observability ride-along (runtime/fleet_observer.py)
    p.add_argument("--digest-period", type=float, default=0.0,
                   help="worker fleet-digest publish period in seconds; "
                        ">0 adds extras.fleet + extras.slo (SLO "
                        "attainment) to the report")
    p.add_argument("--digest-window", type=float, default=60.0,
                   help="fleet observer aggregation window")
    p.add_argument("--slo", default=None,
                   help="burn-rate SLO spec 'phase:pNN<seconds,...' "
                        "(default derives from --ttft-slo/--itl-slo)")
    return p.parse_args(argv)


def main(argv=None) -> GoodputReport:
    args = parse_args(argv)
    if not args.mocker:
        import dynamo_tpu

        dynamo_tpu.enable_compilation_cache()
    report = asyncio.run(run_goodput(args))
    print(report.to_json())
    return report


if __name__ == "__main__":
    main()
