"""dynamo_tpu — TPU-native distributed LLM inference framework.

A ground-up, TPU-first re-design of the capabilities of NVIDIA Dynamo
(reference surveyed in SURVEY.md): an OpenAI-compatible frontend, a
KV-cache-aware smart router, disaggregated prefill/decode serving, a
multi-tier KV block manager, request migration / fault tolerance, an
SLA-driven planner, and — unlike the reference, which wraps external CUDA
engines — a native JAX/XLA/Pallas serving engine with paged attention,
continuous batching, and pjit mesh sharding (DP/TP/EP/SP) over ICI.

Layer map (mirrors reference layers L0–L8, SURVEY.md §1):
  runtime/   — distributed runtime: component model, discovery, request
               plane (TCP/msgpack), event plane (ZMQ), metrics
               (analog of lib/runtime, Rust, in the reference)
  tokens/    — token-block hashing contract (analog of lib/tokens +
               lib/kv-hashing)
  router/    — KV-aware routing: radix indexer, cost-based selection,
               active sequences, event publishing (analog of
               lib/kv-router + lib/llm/src/kv_router)
  frontend/  — OpenAI-compatible HTTP frontend, preprocessor,
               detokenizer/stop handling, migration (analog of lib/llm)
  engine/    — native JAX serving engine: paged KV cache, continuous
               batching scheduler, bucketed jit step functions
               (the reference delegates this to vLLM/SGLang/TRT-LLM)
  models/    — TPU-native model definitions (Llama family first)
  ops/       — Pallas TPU kernels: ragged paged attention, flash
               attention, block copy/permute, ring attention
  parallel/  — device mesh + sharding specs (dp/tp/ep/sp axes)
  kvbm/      — multi-tier KV block manager: G1 HBM / G2 host / G3 disk
  mocker/    — simulated engine with a TPU step-time model (CI without
               TPUs; analog of lib/mocker)
  planner/   — SLA autoscaler control loop (analog of dynamo.planner)
"""

__version__ = "0.1.0"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache (SURVEY.md §5.4 — fast
    replica spin-up; the compiled-program half of fast restart, next to
    the orbax weight snapshot). Every JAX entry point calls this before
    its first jit. ONE rule for where it lives: wherever
    JAX_COMPILATION_CACHE_DIR says (JAX reads the variable itself, so no
    directory is set in code), else `.jax_cache` at the root of this
    checkout — one fixed path, never a temp name: JAX hashes the directory
    into every cache key, so a directory that moves never hits. Zero
    thresholds so even small step programs are cached — a restarted
    worker's first request must not recompile ANY bucket it already
    served. Returns the directory in effect."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
