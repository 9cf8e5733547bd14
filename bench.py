"""Chip benchmark entry. Prints ONE JSON line {"metric", "value", "unit",
"device": {"platform", "kind", "count"}} and exits nonzero when JAX finds
no TPU or anything fails — a number from a CPU or from the mocker is never
printed under a chip metric's name. ROADMAP S1 replaces this with the
workloads table; until then it is the one-cell decode microbench.

Default mode: steady-state batched decode throughput (tokens/second) of
the Llama-3.2-3B configuration in bf16 with the paged KV cache, batch 32
— the per-chip engine hot loop that aggregate goodput is built from.

`--goodput [goodput args...]`: SLO goodput through the REAL serving stack
(frontend pipeline + KV router + TCP request plane + engine) — the
north-star metric shape (BASELINE.md / reference benchmarking.md:449:
output tokens/s over requests meeting TTFT+ITL SLOs). Extra args pass
through to dynamo_tpu.bench.goodput (e.g. --disagg, --quantize int8).
The mocker twin is not a chip measurement: run it as
`python -m dynamo_tpu.bench.goodput --mocker`, not through here.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def require_tpu() -> dict:
    """The device stamp every result carries; SystemExit when the default
    backend is not a TPU (JAX falls back to the CPU silently when libtpu
    finds no chip — a benchmark must not)."""
    import jax

    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()
    dev = jax.devices()
    if dev[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU; JAX found {dev[0].platform} "
            f"({dev[0].device_kind}) — no result printed"
        )
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def goodput_main(argv) -> None:
    import asyncio

    if "--mocker" in argv:
        raise SystemExit(
            "--mocker is the CPU twin, not a chip metric: run "
            "`python -m dynamo_tpu.bench.goodput --mocker`"
        )
    device = require_tpu()
    from dynamo_tpu.bench.goodput import parse_args, run_goodput

    # run directly (not goodput.main) so exactly ONE JSON line is printed
    report = asyncio.run(run_goodput(parse_args(argv)))
    _emit(
        {
            "metric": "slo_goodput",
            "value": round(report.goodput_tok_s, 1),
            "unit": "tok/s",
            "device": device,
        }
    )


def main() -> None:
    if "--goodput" in sys.argv[1:]:
        argv = [a for a in sys.argv[1:] if a != "--goodput"]
        goodput_main(argv)
        return

    # shapes are env-tunable so chip sessions can run the non-toy
    # points: e.g. DYN_BENCH_ISL=1024
    # DYN_BENCH_PAGES=24 for a long-context decode row alongside the
    # default 128-token one; --goodput covers the SLO north-star shape
    B = int(os.environ.get("DYN_BENCH_B", "32"))
    prompt_len = int(os.environ.get("DYN_BENCH_ISL", "128"))
    decode_steps = int(os.environ.get("DYN_BENCH_STEPS", "128"))
    T = int(os.environ.get("DYN_BENCH_T", "32"))
    page_size = 64
    # capacity covers prompt + EVERY generated token: the untimed warmup
    # dispatch also advances positions by T, so (n_dispatch + 1) * T
    total_tokens = prompt_len + (max(decode_steps // T, 1) + 1) * T
    max_pages = int(os.environ.get("DYN_BENCH_PAGES", "0")) or (
        -(-total_tokens // page_size)
    )
    if max_pages * page_size < total_tokens:
        raise SystemExit(
            f"DYN_BENCH_PAGES={max_pages} holds {max_pages * page_size} "
            f"tokens but the run generates {total_tokens}"
        )
    model_name = os.environ.get("DYN_BENCH_MODEL", "llama-3.2-3b")
    metric_name = f"decode_throughput_{model_name}_bf16_b{B}"
    # every shape knob that changes the workload shows up in the metric
    # name, so differently-shaped runs never collide in baseline tracking
    if prompt_len != 128:
        metric_name += f"_isl{prompt_len}"
    if decode_steps != 128:
        metric_name += f"_steps{decode_steps}"
    if T != 32:
        metric_name += f"_t{T}"
    device = require_tpu()

    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.models.config import get_config

    quantize = os.environ.get("DYN_BENCH_QUANTIZE") or None  # e.g. "int8"
    attn_impl = os.environ.get("DYN_BENCH_ATTN") or None  # "jnp" | "pallas"
    kv_quantize = os.environ.get("DYN_BENCH_KV_QUANTIZE") or None  # "int8"
    config = get_config(model_name)
    runner = ModelRunner(
        config,
        num_pages=B * max_pages + 8,
        page_size=page_size,
        max_pages_per_seq=max_pages,
        decode_buckets=(B,),
        prefill_buckets=(prompt_len,),
        seed=0,
        quantize=quantize,
        attn_impl=attn_impl,
        kv_quantize=kv_quantize,
    )

    rng = np.random.default_rng(0)
    sampling = SamplingParams.make(
        temperature=[1.0] * B, top_k=[0] * B, top_p=[1.0] * B, seeds=list(range(B))
    )

    # per-seq page tables (disjoint)
    tables = [list(range(i * max_pages, i * max_pages + max_pages)) for i in range(B)]

    # prefill each sequence once (fills KV to prompt_len)
    for i in range(B):
        prompt = rng.integers(1, config.vocab_size, prompt_len).tolist()
        runner.prefill(prompt, 0, tables[i], prior_len=0)

    tokens = rng.integers(1, config.vocab_size, B).tolist()
    lens = [prompt_len] * B
    # T (fused decode steps per dispatch) was read above for page sizing

    def run_fused(step_idx):
        nonlocal tokens, lens
        out = runner.decode_multi(T, tokens, lens, tables, sampling, step_idx)
        tokens = [int(t) for t in out[:B, -1]]
        lens = [l + T for l in lens]

    # warmup (compile); decode_multi device_gets, which is the honest sync
    run_fused(0)

    n_dispatch = max(decode_steps // T, 1)
    t0 = time.perf_counter()
    for s in range(n_dispatch):
        run_fused(1 + s * T)
    dt = time.perf_counter() - t0

    tok_s = B * n_dispatch * T / dt
    _emit(
        {
            "metric": metric_name,
            "value": round(tok_s, 1),
            "unit": "tok/s",
            "device": device,
        }
    )


if __name__ == "__main__":
    main()
