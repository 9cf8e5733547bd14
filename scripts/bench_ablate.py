"""Decode step-time decomposition on real TPU.

step(L) = fixed + L * per_layer, measured by varying n_layers; plus a
fused-T sweep to expose per-dispatch overhead. Run on the chip:
`python scripts/bench_ablate.py`.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dynamo_tpu.engine.model_runner import ModelRunner
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.models.config import get_config

B = 32
PROMPT = 128
PAGE = 64
MP = 8


def make_runner(config, **kw):
    return ModelRunner(
        config,
        num_pages=B * MP + 8,
        page_size=PAGE,
        max_pages_per_seq=MP,
        decode_buckets=(B,),
        prefill_buckets=(PROMPT,),
        seed=0,
        **kw,
    )


def time_decode(runner, config, T=16, steps=128, sampling=None):
    rng = np.random.default_rng(0)
    if sampling is None:
        sampling = SamplingParams.make(
            temperature=[1.0] * B, top_k=[0] * B, top_p=[1.0] * B,
            seeds=list(range(B)),
        )
    tables = [list(range(i * MP, i * MP + MP)) for i in range(B)]
    for i in range(B):
        prompt = rng.integers(1, config.vocab_size, PROMPT).tolist()
        runner.prefill(prompt, 0, tables[i], prior_len=0)
    tokens = rng.integers(1, config.vocab_size, B).tolist()
    lens = [PROMPT] * B

    def run(step_idx, tok):
        nonlocal lens
        out, last = runner.decode_multi_async(T, tok, lens, tables, sampling, step_idx)
        lens = [min(l + T, MP * PAGE - T - 1) for l in lens]
        return out, last

    import jax

    out, tok = run(0, tokens)  # compile
    np.asarray(jax.device_get(out))
    n = max(steps // T, 1)
    t0 = time.perf_counter()
    for s in range(n):
        out, tok = run(1 + s * T, tok)
    np.asarray(jax.device_get(out))
    dt = time.perf_counter() - t0
    return dt / (n * T) * 1e3  # ms per decode step


def main():
    import dynamo_tpu

    dynamo_tpu.enable_compilation_cache()
    cfg = get_config("llama-3.2-3b")
    base = time_decode(make_runner(cfg), cfg)
    print(f"L=28 T=16 step: {base:.2f} ms", flush=True)

    t64 = time_decode(make_runner(cfg), cfg, T=64, steps=128)
    print(f"L=28 T=64 step: {t64:.2f} ms  (dispatch overhead/step at T=16: "
          f"{(base - t64) * 1.0:.2f} ms)", flush=True)

    import dataclasses

    half = dataclasses.replace(cfg, n_layers=14, name="3b-half")
    h = time_decode(make_runner(half), half)
    per_layer = (base - h) / 14
    fixed = base - 28 * per_layer
    print(f"L=14 T=16 step: {h:.2f} ms -> per-layer {per_layer * 1e3:.0f} us, "
          f"fixed (embed+head+sample+dispatch) {fixed:.2f} ms", flush=True)

    greedy = SamplingParams.make(
        temperature=[0.0] * B, top_k=[0] * B, top_p=[1.0] * B,
        seeds=list(range(B)),
    )
    g = time_decode(make_runner(cfg), cfg, sampling=greedy)
    print(f"L=28 greedy step: {g:.2f} ms (sampling cost {base - g:.2f} ms)",
          flush=True)


if __name__ == "__main__":
    main()
