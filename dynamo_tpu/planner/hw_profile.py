"""`python -m dynamo_tpu.planner.hw_profile` — measure the REAL engine.

Analog of the reference profiler's hardware mode (docs/components/profiler/
README.md:8-60: sweep configs on real accelerators, persist interpolation
data the planner consumes — `thorough.py`'s role; the SimTiming sweep in
planner/profiler.py is the `rapid.py` analog). This closes the round-2
"circular perf model" gap: TpuPerfModel used to scale the mocker's GUESSED
constants, so planner capacity inherited whatever the sim assumed. This
module times the actual ModelRunner on whatever backend JAX has — the real
chip when present — and persists a profile artifact that `TpuPerfModel`,
`SimTiming` and the planner load instead of the guesses.

Artifact (JSON): measured (batch → decode step time) and (chunk tokens →
prefill time) point tables per variant (attn impl × kv quant), plus a
least-squares fit of the linear step-time model and the derived per-chip
decode capacity. Run on the chip:

    python -m dynamo_tpu.planner.hw_profile --model llama32-3b \
        --checkpoint /path/to/ckpt --out docs/profiles/llama32-3b-v5e.json
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

PROFILE_VERSION = 1


def fit_line(points, d0: float, s0: float):
    """(intercept, slope) via least squares over (x, y) pairs; falls back
    to (d0, s0) with fewer than two distinct x. Shared with SimTiming.fit
    (mocker/sim.py) — one fitting routine for every step-time model."""
    points = list(points)
    if len(points) < 2 or len({p[0] for p in points}) < 2:
        return d0, s0
    xs = np.asarray([p[0] for p in points], float)
    ys = np.asarray([p[1] for p in points], float)
    slope, intercept = np.polyfit(xs, ys, 1)
    return max(float(intercept), 0.0), max(float(slope), 0.0)


def run_hw_sweep(
    model: str = "tiny",
    *,
    checkpoint: Optional[str] = None,
    batches: Sequence[int] = (1, 2, 4, 8, 16, 32),
    prefill_chunks: Sequence[int] = (64, 128, 256, 512),
    page_size: int = 16,
    num_pages: int = 512,
    max_seq_len: int = 2048,
    decode_steps: int = 8,
    attn_impls: Optional[Sequence[Optional[str]]] = None,
    kv_quants: Sequence[Optional[str]] = (None,),
    warmup: int = 1,
    iters: int = 3,
) -> Dict[str, Any]:
    """Time real prefill/decode dispatches across (batch, chunk, attn
    impl, kv quant). Each timing excludes compilation (warmup dispatch
    first) and is the median of `iters` repeats. Returns the profile
    artifact dict (save with save_profile)."""
    import jax

    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config

    if checkpoint:
        from dynamo_tpu.engine.weights import config_from_hf, load_hf_checkpoint

        config = config_from_hf(checkpoint, name=model)
        params = load_hf_checkpoint(checkpoint, config)
    else:
        config = get_config(model)
        params = None

    platform = jax.devices()[0].platform
    if attn_impls is None:
        # pallas needs a real accelerator; jnp runs anywhere
        attn_impls = ("pallas", "jnp") if platform != "cpu" else ("jnp",)

    max_pages_per_seq = -(-max_seq_len // page_size)
    decode_buckets = tuple(sorted({b for b in batches}))
    prefill_buckets = tuple(sorted({c for c in prefill_chunks}))
    variants: Dict[str, Any] = {}
    for impl in attn_impls:
        for kvq in kv_quants:
            key = f"{impl or 'auto'}" + (f"+kv_{kvq}" if kvq else "")
            runner = ModelRunner(
                config,
                num_pages=num_pages,
                page_size=page_size,
                max_pages_per_seq=max_pages_per_seq,
                decode_buckets=decode_buckets,
                prefill_buckets=prefill_buckets,
                params=params,
                attn_impl=impl,
                kv_quantize=kvq,
            )
            sampling = lambda n: {  # noqa: E731
                "temperature": [0.0] * n, "top_k": [0] * n,
                "top_p": [1.0] * n, "seeds": [0] * n,
            }
            decode_pts: List[List[float]] = []
            # each sequence decodes decode_steps tokens starting at
            # position 4 — size its page-table row to cover every KV slot
            # it will touch; ids past num_pages would be silently clamped
            # by XLA and the timing would measure aliased nonsense
            pos0 = 4
            seq_pages = -(-(pos0 + decode_steps + 1) // page_size)
            if seq_pages > max_pages_per_seq:
                raise ValueError(
                    f"decode_steps={decode_steps} needs {seq_pages} pages/seq "
                    f"but max_seq_len={max_seq_len} allows {max_pages_per_seq} "
                    "— clamping would silently measure out-of-range addressing"
                )
            for B in batches:
                if B * seq_pages > num_pages:
                    continue  # inputs may be unsorted; later Bs might fit
                tables = [
                    list(range(i * seq_pages, (i + 1) * seq_pages))
                    for i in range(B)
                ]
                args = (
                    decode_steps, [1] * B, [pos0] * B, tables, sampling(B), 1,
                )
                ts = []
                for it in range(warmup + iters):
                    t0 = time.perf_counter()
                    runner.decode_multi(*args)
                    dt = time.perf_counter() - t0
                    if it >= warmup:
                        ts.append(dt)
                # per-STEP time at this batch
                decode_pts.append([float(B), float(np.median(ts)) / decode_steps])

            prefill_pts: List[List[float]] = []
            for chunk in prefill_chunks:
                chunk_pages = -(-chunk // page_size)
                if chunk > max_seq_len or chunk_pages > min(num_pages, max_pages_per_seq):
                    continue
                row = list(range(chunk_pages))
                toks = [i % config.vocab_size for i in range(chunk)]
                ts = []
                for it in range(warmup + iters):
                    t0 = time.perf_counter()
                    out = runner.prefill(toks, 0, row, 0)
                    out.block_until_ready()
                    dt = time.perf_counter() - t0
                    if it >= warmup:
                        ts.append(dt)
                prefill_pts.append([float(chunk), float(np.median(ts))])

            if not decode_pts or not prefill_pts:
                raise ValueError(
                    f"nothing measurable: batches={list(batches)} need "
                    f"B*{seq_pages} <= num_pages={num_pages}; chunks="
                    f"{list(prefill_chunks)} need <= max_seq_len={max_seq_len} "
                    f"and ceil(chunk/{page_size}) <= "
                    f"{min(num_pages, max_pages_per_seq)}"
                )
            d_base, d_slope = fit_line(decode_pts, 0.004, 0.0003)
            p_base, p_slope = fit_line(prefill_pts, 0.004, 0.00004)
            cap_b, cap_t = max(decode_pts, key=lambda p: p[0])
            pre_b, pre_t = max(prefill_pts, key=lambda p: p[0])
            variants[key] = {
                "decode": decode_pts,  # [batch, s_per_step]
                "prefill": prefill_pts,  # [chunk_tokens, s]
                "fit": {
                    "decode_base_s": d_base,
                    "decode_per_seq_s": d_slope,
                    "prefill_base_s": p_base,
                    "prefill_per_token_s": p_slope,
                    # best measured per-replica throughputs — the
                    # planner's cold-start capacity floors, per component
                    "decode_capacity_tok_s": cap_b / cap_t if cap_t > 0 else 0.0,
                    "prefill_capacity_tok_s": pre_b / pre_t if pre_t > 0 else 0.0,
                },
            }
            del runner

    best = max(
        variants, key=lambda k: variants[k]["fit"]["decode_capacity_tok_s"]
    )
    return {
        "version": PROFILE_VERSION,
        "model": config.name,
        "platform": platform,
        "device": str(jax.devices()[0]),
        "page_size": page_size,
        "decode_steps": decode_steps,
        "best_variant": best,
        "variants": variants,
    }


def save_profile(profile: Dict[str, Any], path: str) -> None:
    with open(path, "w") as f:
        json.dump(profile, f, indent=1)


def load_profile(path: str) -> Dict[str, Any]:
    with open(path) as f:
        profile = json.load(f)
    if profile.get("version") != PROFILE_VERSION:
        raise ValueError(
            f"profile version {profile.get('version')} != {PROFILE_VERSION}"
        )
    return profile


def profile_fit(profile: Dict[str, Any], variant: Optional[str] = None) -> Dict[str, float]:
    """The fitted step-time constants of `variant` (default: the
    best-throughput variant recorded in the artifact)."""
    v = variant or profile["best_variant"]
    return profile["variants"][v]["fit"]


def main(argv=None) -> None:
    p = argparse.ArgumentParser("dynamo_tpu.planner.hw_profile")
    p.add_argument("--model", default="tiny")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--out", required=True, help="profile artifact path (JSON)")
    p.add_argument("--batches", default="1,2,4,8,16,32")
    p.add_argument("--prefill-chunks", default="64,128,256,512")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--max-seq-len", type=int, default=2048)
    p.add_argument("--decode-steps", type=int, default=8)
    p.add_argument("--kv-int8", action="store_true",
                   help="also sweep int8-quantized KV pools")
    p.add_argument("--iters", type=int, default=3)
    args = p.parse_args(argv)

    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()
    profile = run_hw_sweep(
        args.model,
        checkpoint=args.checkpoint,
        batches=[int(x) for x in args.batches.split(",")],
        prefill_chunks=[int(x) for x in args.prefill_chunks.split(",")],
        page_size=args.page_size,
        num_pages=args.num_pages,
        max_seq_len=args.max_seq_len,
        decode_steps=args.decode_steps,
        kv_quants=(None, "int8") if args.kv_int8 else (None,),
        iters=args.iters,
    )
    save_profile(profile, args.out)
    fit = profile_fit(profile)
    print(json.dumps({
        "out": args.out,
        "best_variant": profile["best_variant"],
        **{k: round(v, 6) for k, v in fit.items()},
    }))


if __name__ == "__main__":
    main()
