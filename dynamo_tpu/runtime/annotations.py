"""Device-timeline trace annotations — the TPU analog of the reference's
NVTX integration (lib/runtime/Cargo.toml:24-27, src/nvtx.rs: Nsight
ranges, compile-time + `DYN_ENABLE_RUST_NVTX` runtime gated, ~1ns off).

On TPU the profiler is XLA's: `jax.profiler.start_server` exposes the
worker to TensorBoard/xprof capture, and `TraceAnnotation` ranges put
the engine iteration's host work (`engine.wait/inbox/schedule`, a step
parent `engine.decode/mixed/prefill/...` tiled by `engine.prep/stage/
dispatch/readback/emit`, then `engine.publish`, and `engine.deliver`
wherever what a commit left for the clients and observers is handed
over: under the next program) on the captured host+device timeline, so
every idle gap on the device has an owner.
Gated by `DYN_ENABLE_JAX_TRACE=1`; when off, `annotate` returns one
shared no-op context manager (a cached check, no allocation per call).

The door. The step thread's phases (`PHASES`: the children the trace
reduction owns idle gaps by) do not call `annotate` themselves: they go
through `phase(INBOX)` ... `phase(WAIT)`, which opens the very same
`TraceAnnotation` when the gate is on and ALWAYS adds the phase's seconds
to the `StepClock` bound to the calling thread, so the names the profiler
sees and the names an `IterationRecord` carries (`host_<phase>_s`) are the
same by construction, on every run, traced or not. The engine binds one
clock to its step thread (`bind_clock`) while its flight recorder is on,
closes its interval at each commit mark (`StepClock.cut`) and has the
recorder, which owns the record, empty it (`FlightRecorder.take_clock`:
the record's fields and /metrics; this module knows neither); a thread
with no clock bound (a warm-up walk, a script, a test, another replica's
builder) gets plain `annotate` and nothing else happens. Step parents
(`engine.decode/mixed/prefill/prefill_packed/spec_verify`) stay plain
`annotate` calls.

A phase costs two clock reads into preallocated slots: no allocation,
no lock, no formatting. Phases nest as the spans do and the innermost owns
its seconds (the trace reduction's rule), so the slots never count a
second twice and add up to at most the interval they were taken over.

Exposed or hidden. The clock knows whether the step thread has work
enqueued on the device and not collected: `handles` (decode dispatches
the engine holds in flight, from `Runner.decode_dispatch`'s return to
`decode_collect`'s; the engine counts them) or `serial` (a jit call,
`phase(DISPATCH)`, since the last blocking read, `phase(READBACK)` or a
`synced()` the runner calls after a `device_get` outside one; the door
sets it). A phase's seconds that run with neither are *exposed*: the
device had nothing from this thread, so it was provably idle for want of
the host. That is judged when the phase starts and again whenever it can
change while the phase is open: when a phase inside it ends (a `dispatch`
sets `serial`, a `readback` clears it) and at `synced()` (the first token
of a prompt is read inside `emit`: what `emit` runs after it is exposed).
Everything else is hidden: under a host-bound run-ahead iteration the
device may still idle, and the device trace stays the judge of that.
A readback is never exposed (it waits for the device, not the device for
it). A prefill chunk that sampled nothing is not read back, so it stays
enqueued until the next readback."""

from __future__ import annotations

import contextlib
import functools
import gc
import logging
import os
import threading
import time

log = logging.getLogger("dynamo_tpu.annotations")

_TRUTHY = {"1", "true", "on", "yes"}  # lib/truthy semantics


@functools.lru_cache(maxsize=1)
def _enabled() -> bool:
    # cached: the engine step loop calls annotate() per plan; the env gate
    # is a deployment decision, not a per-request one (tests reset via
    # _enabled.cache_clear())
    return os.environ.get("DYN_ENABLE_JAX_TRACE", "").lower() in _TRUTHY


_NULL = contextlib.nullcontext()


def _trace_annotation(name: str, **kwargs):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **kwargs)


def annotate(name: str, **kwargs):
    """Context manager marking a named range on the profiler timeline.
    kwargs become xprof metadata (e.g. batch size, token counts)."""
    if not _enabled():
        return _NULL
    return _trace_annotation(name, **kwargs)


# -- the door: the step thread's phases -------------------------------------

PHASES = ("inbox", "schedule", "prep", "stage", "dispatch", "readback",
          "emit", "publish", "deliver", "wait")
(INBOX, SCHEDULE, PREP, STAGE, DISPATCH, READBACK, EMIT, PUBLISH, DELIVER,
 WAIT) = range(10)
SPAN_NAMES = tuple("engine." + p for p in PHASES)
# an iteration's own: `wait` is timed, and belongs to no iteration
RECORD_PHASES = PHASES[:WAIT]
_DEPTH = 16  # open phases at once (the spans nest two or three deep)

_clock = time.monotonic_ns  # the clock of the engine's commit marks
_tls = threading.local()


class _Phase:
    """One phase of one clock, as a context manager that is made once."""

    __slots__ = ("clock", "idx", "name", "kw")

    def __init__(self, clock: "StepClock", idx: int):
        self.clock, self.idx, self.name = clock, idx, SPAN_NAMES[idx]
        self.kw = None

    def __enter__(self):
        c = self.clock
        d = c._depth
        if _enabled():
            kw, self.kw = self.kw, None
            ann = _trace_annotation(self.name, **(kw or {}))
            ann.__enter__()
            c._ann[d] = ann
        now = _clock()
        if d:  # the innermost owns: the phase around this one stops here
            i = c._open[d - 1]
            dt = now - c._t
            c.ns[i] += dt
            if c._exp[d - 1]:
                c.exposed_ns[i] += dt
        i = self.idx
        c._open[d] = i
        c._exp[d] = not (c.handles or c.serial or i == READBACK)
        c._depth = d + 1
        c._t = now

    def __exit__(self, *exc):
        c = self.clock
        now = _clock()
        d = c._depth - 1
        i = self.idx
        dt = now - c._t
        c.ns[i] += dt
        if c._exp[d]:
            c.exposed_ns[i] += dt
        c._depth = d
        c._t = now
        if i == DISPATCH:
            c.serial = True
        elif i == READBACK:  # (a runner that reads back otherwise: synced())
            c.serial = False
        if d:  # the phase around this one goes on from here, judged anew
            c._exp[d - 1] = not (
                c.handles or c.serial or c._open[d - 1] == READBACK)
        ann = c._ann[d]
        if ann is not None:
            c._ann[d] = None
            ann.__exit__(*exc)
        return False


class StepClock:
    """One engine's host clock: nanoseconds by `PHASES` index since the
    last `clear` (`ns`, and in `exposed_ns` the part of them that ran with
    nothing enqueued on the device). Written and read by the one thread
    it is bound to: the door adds, `cut` closes the interval at a commit
    mark, whoever owns the record reads the lists and clears them."""

    __slots__ = ("ns", "exposed_ns", "gc_ns", "handles", "serial",
                 "_open", "_exp", "_ann", "_depth", "_t", "_gc_t", "_phases")

    def __init__(self):
        n = len(PHASES)
        self.ns = [0] * n
        self.exposed_ns = [0] * n
        self.gc_ns = 0       # collections on the bound thread, same interval
        self.handles = 0     # decode dispatches in flight (the engine's count)
        self.serial = False  # a jit call since the last blocking read
        self._open = [0] * _DEPTH
        self._exp = [False] * _DEPTH
        self._ann = [None] * _DEPTH
        self._depth = 0
        self._t = 0
        self._gc_t = 0
        self._phases = tuple(_Phase(self, i) for i in range(n))

    def clear(self, wait: bool = False) -> None:
        """Forget the interval so far (the loop found nothing to do: the
        seconds since the last commit belong to no iteration). `wait`: the
        idle sleeps too, which are in no interval: whoever took them."""
        ns, ex = self.ns, self.exposed_ns
        for i in range(len(ns) if wait else WAIT):
            ns[i] = ex[i] = 0
        self.gc_ns = 0

    def cut(self, now_ns: int) -> None:
        """Close the interval at `now_ns` (the engine's commit mark, on
        `time.monotonic_ns`): a phase open at the mark (`publish` is) is
        cut there, what it has run is in the lists and the rest goes to
        the next interval, which starts at `clear`."""
        d = self._depth
        if d:
            i = self._open[d - 1]
            dt = now_ns - self._t
            self.ns[i] += dt
            if self._exp[d - 1]:
                self.exposed_ns[i] += dt
            self._t = now_ns


def phase(idx: int, **kw):
    """The door: `with phase(STAGE): ...`. On a thread with a clock bound
    the phase's seconds go to the clock and, with the profiler gate on,
    the span `engine.<phase>` opens exactly as `annotate` would open it;
    on any other thread it is `annotate(SPAN_NAMES[idx], **kw)`."""
    clock = getattr(_tls, "clock", None)
    if clock is None:
        return annotate(SPAN_NAMES[idx], **kw)
    p = clock._phases[idx]
    if kw and _enabled():  # gate off: the metadata is dropped here, not kept
        p.kw = kw          # alive until the phase's next call
    return p


def synced() -> None:
    """The calling thread has just blocked on the device for the newest
    of its serial calls (a `device_get`, inside a `readback` phase or not:
    the first token of a prompt is sampled and read inside `emit`), so
    none of them is enqueued any more. No span; the phase open around
    the read is judged anew from here."""
    c = getattr(_tls, "clock", None)
    if c is not None:
        c.serial = False
        if c._depth:
            c.cut(_clock())
            d = c._depth - 1
            c._exp[d] = not (c.handles or c._open[d] == READBACK)


def _on_gc(when: str, info) -> None:
    # gc.callbacks run on the thread that triggered the collection: only
    # one with a clock bound counts, and it counts its own
    clock = getattr(_tls, "clock", None)
    if clock is not None:
        if when == "start":
            clock._gc_t = _clock()
        elif clock._gc_t:
            clock.gc_ns += _clock() - clock._gc_t
            clock._gc_t = 0


def bind_clock(clock: StepClock) -> None:
    """Bind `clock` to the calling thread (an engine's step thread, at the
    top of its loop) until `unbind_clock`."""
    _tls.clock = clock
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def unbind_clock() -> None:
    _tls.clock = None


def start_profiler_server(port: int) -> None:
    """Start the XLA profiler server (TensorBoard 'capture profile'
    target). Raises what jax raises: a worker asked for `--profiler-port`
    that cannot serve it must not come up without one."""
    import jax

    jax.profiler.start_server(port)
    log.info("jax profiler server on port %d", port)
