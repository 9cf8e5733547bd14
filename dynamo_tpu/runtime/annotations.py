"""Device-timeline trace annotations — the TPU analog of the reference's
NVTX integration (lib/runtime/Cargo.toml:24-27, src/nvtx.rs: Nsight
ranges, compile-time + `DYN_ENABLE_RUST_NVTX` runtime gated, ~1ns off).

On TPU the profiler is XLA's: `jax.profiler.start_server` exposes the
worker to TensorBoard/xprof capture, and `TraceAnnotation` ranges put
the engine iteration's host work (`engine.wait/inbox/schedule`, a step
parent `engine.decode/mixed/prefill/...` tiled by `engine.prep/stage/
dispatch/readback/emit`, then `engine.publish`) on the captured
host+device timeline, so every idle gap on the device has an owner.
Gated by `DYN_ENABLE_JAX_TRACE=1`; when off, `annotate` returns one
shared no-op context manager (a cached check, no allocation per call)."""

from __future__ import annotations

import contextlib
import functools
import logging
import os

log = logging.getLogger("dynamo_tpu.annotations")

_TRUTHY = {"1", "true", "on", "yes"}  # lib/truthy semantics


@functools.lru_cache(maxsize=1)
def _enabled() -> bool:
    # cached: the engine step loop calls annotate() per plan; the env gate
    # is a deployment decision, not a per-request one (tests reset via
    # _enabled.cache_clear())
    return os.environ.get("DYN_ENABLE_JAX_TRACE", "").lower() in _TRUTHY


_NULL = contextlib.nullcontext()


def annotate(name: str, **kwargs):
    """Context manager marking a named range on the profiler timeline.
    kwargs become xprof metadata (e.g. batch size, token counts)."""
    if not _enabled():
        return _NULL
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **kwargs)


def start_profiler_server(port: int) -> None:
    """Start the XLA profiler server (TensorBoard 'capture profile'
    target). Raises what jax raises: a worker asked for `--profiler-port`
    that cannot serve it must not come up without one."""
    import jax

    jax.profiler.start_server(port)
    log.info("jax profiler server on port %d", port)
