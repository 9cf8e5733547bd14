"""`python -m dynamo_tpu.planner.profiler` — SLA-driven config sweep.

Analog of the reference profiler subsystem (benchmarks/profiler/: sweep
parallelism/batch configs against a workload, measure TTFT/ITL, recommend
the deployment that meets the SLA at the best per-accelerator goodput —
the input the SLA planner deploys from).

TPU version: each candidate config (tensor-parallel degree x number of
workers on a fixed chip budget) is evaluated by replaying a workload trace
against an in-process stack — real scheduler, page pool, router, frontend
chain; SimRunner accelerator with a TP-scaled step-time model. The scaling
model is the standard roofline intuition: per-step time shrinks ~1/tp with
an ICI efficiency exponent, while the dispatch floor stays constant (so
over-sharding small models profiles as the loss it really is).

Output: one JSON line per config plus a `recommendation` line; exits
nonzero if nothing meets the SLA at the requested attainment.
"""

from __future__ import annotations

import argparse
import asyncio
import json
from dataclasses import dataclass
from typing import List, Optional

from dynamo_tpu.bench.loadgen import (
    compute_goodput,
    generate_trace,
    load_trace,
    run_trace_against_engine,
)
from dynamo_tpu.engine.engine import InferenceEngine
from dynamo_tpu.frontend.protocols import ModelCard
from dynamo_tpu.frontend.service import ModelManager, ModelWatcher
from dynamo_tpu.mocker.sim import SimRunner, SimTiming
from dynamo_tpu.runtime.discovery import MemDiscovery
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.logging_util import configure_logging
from dynamo_tpu.worker_common import serve_worker


@dataclass
class TpuPerfModel:
    """Single-chip step-time baseline + parallelism scaling. The defaults
    are HAND-SET constants (the same ones as mocker/sim.py SimTiming), not
    measurements: nothing here was ever fitted to a chip (ROADMAP S9).
    Override per model, or fit from a hardware profile
    (planner/hw_profile.py) before trusting a recommendation."""

    decode_base_s: float = 0.004
    decode_per_seq_s: float = 0.0003
    prefill_base_s: float = 0.004
    prefill_per_token_s: float = 0.00004
    dispatch_overhead_s: float = 0.002
    tp_efficiency: float = 0.85  # per-step time ~ 1/tp**tp_efficiency

    def timing_for(self, tp: int, speed: float = 1.0) -> SimTiming:
        s = 1.0 / (tp**self.tp_efficiency)
        return SimTiming(
            prefill_base_s=self.prefill_base_s * s,
            prefill_per_token_s=self.prefill_per_token_s * s,
            decode_base_s=self.decode_base_s * s,
            decode_per_seq_s=self.decode_per_seq_s * s,
            dispatch_overhead_s=self.dispatch_overhead_s,  # host-side floor
            speed=speed,
        )

    @classmethod
    def from_profile(cls, profile, variant: str = None, **overrides) -> "TpuPerfModel":
        """Baselines MEASURED on hardware (planner/hw_profile.py artifact
        or its path) instead of the sim's guessed constants — the de-
        circularized path: engine → profile → perf model → capacity."""
        from dynamo_tpu.planner.hw_profile import load_profile, profile_fit

        if isinstance(profile, str):
            profile = load_profile(profile)
        fit = profile_fit(profile, variant)
        # the measured wall-clock per dispatch already contains the host
        # dispatch overhead (folded into the fitted intercepts) — adding
        # the default 2ms again would double-count it
        overrides.setdefault("dispatch_overhead_s", 0.0)
        return cls(
            decode_base_s=fit["decode_base_s"],
            decode_per_seq_s=fit["decode_per_seq_s"],
            prefill_base_s=fit["prefill_base_s"],
            prefill_per_token_s=fit["prefill_per_token_s"],
            **overrides,
        )


@dataclass
class ConfigResult:
    tp: int
    workers: int
    chips: int
    report: dict  # GoodputReport fields
    attainment: float
    goodput_per_chip: float

    def to_dict(self) -> dict:
        return {
            "tp": self.tp,
            "workers": self.workers,
            "chips": self.chips,
            "attainment": round(self.attainment, 4),
            "goodput_per_chip": round(self.goodput_per_chip, 2),
            **self.report,
        }


async def _evaluate_config(
    tp: int,
    n_workers: int,
    perf: TpuPerfModel,
    trace,
    *,
    router_mode: str,
    ttft_slo: float,
    itl_slo: float,
    speed: float,
    page_size: int,
    seed: int,
) -> ConfigResult:
    realm = f"profiler-{tp}x{n_workers}-{seed}"
    workers = []
    for _ in range(n_workers):
        rt = DistributedRuntime(discovery=MemDiscovery(realm=realm), event_transport="inproc")
        runner = SimRunner(page_size=page_size, timing=perf.timing_for(tp, speed))
        engine = InferenceEngine(runner, chunk_size=512, decode_steps=4)
        card = ModelCard(
            name="profile-model", tokenizer="byte",
            context_length=4096, kv_block_size=page_size,
        )
        w = await serve_worker(rt, engine, card)
        workers.append((rt, w))

    frt = DistributedRuntime(discovery=MemDiscovery(realm=realm), event_transport="inproc")
    manager = ModelManager()
    watcher = ModelWatcher(frt, manager, router_mode=router_mode)
    await watcher.start()
    try:
        await watcher.wait_for_model(timeout=10)
        entry = manager.get("profile-model")
        results, duration = await run_trace_against_engine(
            trace, entry.chain.generate, time_scale=speed, seed=seed
        )
        report = compute_goodput(results, duration, ttft_slo * speed, itl_slo * speed)
        attainment = report.n_slo_met / max(report.n_ok, 1)
        # goodput is measured on the compressed clock; rescale to real time
        goodput = report.goodput_tok_s * speed
        return ConfigResult(
            tp=tp,
            workers=n_workers,
            chips=tp * n_workers,
            report=json.loads(report.to_json()),
            attainment=attainment,
            goodput_per_chip=goodput / (tp * n_workers),
        )
    finally:
        await watcher.stop()
        await frt.shutdown()
        for rt, w in workers:
            await w.stop()
            await rt.shutdown(drain_timeout=1)


async def sweep(args) -> dict:
    if getattr(args, "hw_profile", None):
        perf = TpuPerfModel.from_profile(
            args.hw_profile, tp_efficiency=args.tp_efficiency
        )
    else:
        perf = TpuPerfModel(
            decode_base_s=args.decode_base_ms / 1000.0,
            tp_efficiency=args.tp_efficiency,
        )
    if args.trace:
        trace = load_trace(args.trace)
    else:
        trace = generate_trace(
            args.requests, args.rps, isl_mean=args.isl, osl_mean=args.osl,
            prefix_groups=args.prefix_groups, seed=args.seed,
        )

    tps = [t for t in (1, 2, 4, 8, 16) if t <= args.chips and args.chips % t == 0]
    results: List[ConfigResult] = []
    for tp in tps:
        r = await _evaluate_config(
            tp, args.chips // tp, perf, trace,
            router_mode=args.router_mode, ttft_slo=args.ttft_slo,
            itl_slo=args.itl_slo, speed=args.speed,
            page_size=args.page_size, seed=args.seed,
        )
        results.append(r)
        print(json.dumps({"config": r.to_dict()}), flush=True)

    eligible = [r for r in results if r.attainment >= args.min_attainment]
    rec: Optional[ConfigResult] = max(
        eligible, key=lambda r: r.goodput_per_chip, default=None
    )
    out = {
        "chips": args.chips,
        "slo": {"ttft_s": args.ttft_slo, "itl_s": args.itl_slo,
                "min_attainment": args.min_attainment},
        "configs": [r.to_dict() for r in results],
        "recommendation": rec.to_dict() if rec else None,
    }
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser("dynamo_tpu.planner.profiler")
    p.add_argument("--chips", type=int, default=8, help="accelerator budget")
    p.add_argument("--ttft-slo", type=float, default=0.5)
    p.add_argument("--itl-slo", type=float, default=0.05)
    p.add_argument("--min-attainment", type=float, default=0.9)
    p.add_argument("--router-mode", default="kv",
                   choices=["round_robin", "random", "kv"])
    p.add_argument("--trace", default=None)
    p.add_argument("--requests", type=int, default=100)
    p.add_argument("--rps", type=float, default=30.0)
    p.add_argument("--isl", type=int, default=256)
    p.add_argument("--osl", type=int, default=64)
    p.add_argument("--prefix-groups", type=int, default=0)
    p.add_argument("--decode-base-ms", type=float, default=4.0)
    p.add_argument("--hw-profile", default=None,
                   help="hardware profile artifact (planner/hw_profile.py) "
                        "to base step times on instead of the defaults")
    p.add_argument("--tp-efficiency", type=float, default=0.85)
    p.add_argument("--speed", type=float, default=1.0,
                   help="sim clock compression (<1 runs the sweep faster)")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args(argv)


def main(argv=None) -> None:
    configure_logging()
    args = parse_args(argv)
    out = asyncio.run(sweep(args))
    print(json.dumps(out))
    if out["recommendation"] is None:
        raise SystemExit(2)


if __name__ == "__main__":
    main()
