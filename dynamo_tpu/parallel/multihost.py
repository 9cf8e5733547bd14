"""Multi-process / multi-host worker groups.

Reference: multi-node engine grouping — `MultiNodeConfig`
(lib/llm/src/engines.rs:38) and the Grove PodCliqueSet topology
(docs/design-docs/architecture.md:120–129) give the reference leader/worker
process groups whose GPUs form one logical engine. The TPU-native analog:
the group's processes join ONE `jax.distributed` global mesh (a v5e-64
slice = 16 hosts x 4 chips), jitted step functions run SPMD across all of
them, and XLA moves activations/KV over ICI.

Control flow is leader-driven, mirroring the reference's MPI-style ranks:

- process 0 (leader) runs the full serving stack — discovery, request
  plane, scheduler, engine. Its ModelRunner is wrapped in
  `ReplicatingRunner`, which broadcasts every device-touching call over a
  TCP "step plane" before executing it locally.
- processes 1..n-1 (followers) build the identical ModelRunner (same
  config/seed/checkpoint → identical params) and replay the leader's call
  stream via `follower_loop`. Every process therefore enqueues the same
  XLA programs in the same order, which is exactly what SPMD execution
  over a shared mesh requires; the collectives inside the programs
  synchronize the actual compute.

The step plane is intentionally tiny — length-prefixed msgpack frames of
(method, args, kwargs) — because everything that crosses it is host-side
metadata (token ids, page tables, sampling params). Bulk tensor traffic
(weights, KV, activations) never touches it: that all rides ICI inside
XLA programs.
"""

from __future__ import annotations

import logging
import os
import socket
import struct
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import msgpack
import numpy as np

from dynamo_tpu.engine.runner_api import DEVICE_STEPS, MixedOut, Runner

log = logging.getLogger("dynamo_tpu.multihost")

_HDR = struct.Struct("<I")


@dataclass(frozen=True)
class MultihostSpec:
    """One process's membership in a worker group."""

    coordinator: str  # host:port of the jax.distributed coordinator (rank 0)
    num_processes: int
    process_id: int
    step_port: int  # leader's step-plane listen port
    local_devices: Optional[int] = None  # virtual CPU devices (tests)

    @property
    def is_leader(self) -> bool:
        return self.process_id == 0

    @property
    def leader_host(self) -> str:
        return self.coordinator.rsplit(":", 1)[0]


def initialize(spec: MultihostSpec) -> None:
    """Join the group's global device mesh (jax.distributed). Must run
    before any other jax API touches a backend. On CPU (tests), each
    process contributes `local_devices` virtual devices."""
    if spec.local_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={spec.local_devices}"
            ).strip()
    import jax

    jax.distributed.initialize(
        coordinator_address=spec.coordinator,
        num_processes=spec.num_processes,
        process_id=spec.process_id,
    )
    log.info(
        "multihost: process %d/%d joined; %d local / %d global devices",
        spec.process_id, spec.num_processes,
        jax.local_device_count(), jax.device_count(),
    )


# -- wire codec --------------------------------------------------------------


def _enc_default(obj):
    if isinstance(obj, np.ndarray):
        return {
            "__nd__": True,
            "s": list(obj.shape),
            "d": str(obj.dtype),
            "b": np.ascontiguousarray(obj).tobytes(),
        }
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    raise TypeError(f"step plane cannot encode {type(obj)}")


def _dec_hook(obj):
    if obj.get("__nd__"):
        import ml_dtypes

        name = obj["d"]
        dt = np.dtype(ml_dtypes.bfloat16) if "bfloat16" in name else np.dtype(name)
        return np.frombuffer(obj["b"], dtype=dt).reshape(obj["s"])
    return obj


def _pack(msg) -> bytes:
    body = msgpack.packb(msg, default=_enc_default, use_bin_type=True)
    return _HDR.pack(len(body)) + body


def _recv_frame(sock: socket.socket):
    hdr = _recv_exact(sock, _HDR.size)
    if hdr is None:
        return None
    (n,) = _HDR.unpack(hdr)
    body = _recv_exact(sock, n)
    if body is None:
        return None
    return msgpack.unpackb(body, object_hook=_dec_hook, raw=False,
                           strict_map_key=False)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


# -- step plane ---------------------------------------------------------------


class GroupBroken(RuntimeError):
    """A group member died. The group CANNOT limp along: the next XLA
    program's collectives would wait on the dead rank forever, so the
    correct response is fail-fast — the leader fails in-flight requests,
    exits, the followers see its socket close and exit too, and the
    supervisor (k8s operator / systemd) restarts the whole group. In-flight
    requests migrate to other workers via the frontend's Migration
    operator, same as any worker death."""


class StepPlaneLeader:
    """Leader side: accepts follower connections, broadcasts call frames.

    Fire-and-forget (TCP ordering is the sequencing guarantee); followers
    that fall behind catch up — the XLA collectives inside the replayed
    programs are the actual synchronization barrier."""

    def __init__(self, port: int, n_followers: int, accept_timeout: float = 120.0):
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("0.0.0.0", port))
        self._srv.listen(n_followers)
        self.port = self._srv.getsockname()[1]
        self._conns: List[socket.socket] = []
        self._n = n_followers
        self._timeout = accept_timeout
        self._lock = threading.Lock()

    def wait_followers(self) -> None:
        self._srv.settimeout(self._timeout)
        while len(self._conns) < self._n:
            conn, addr = self._srv.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            hello = _recv_frame(conn)
            log.info("step plane: follower %s joined from %s", hello, addr)
            self._conns.append(conn)

    def broadcast(self, method: str, args: tuple, kwargs: dict) -> None:
        frame = _pack([method, list(args), kwargs])
        with self._lock:
            for c in self._conns:
                try:
                    c.sendall(frame)
                except OSError as e:
                    # a dead follower breaks the group (see GroupBroken);
                    # detect it HERE, before enqueuing the local program
                    # whose collectives would hang on the missing rank
                    raise GroupBroken(
                        f"step-plane send to a follower failed: {e}"
                    ) from e

    def close(self) -> None:
        with self._lock:
            for c in self._conns:
                try:
                    c.sendall(_pack(["__stop__", [], {}]))
                    c.close()
                except OSError:
                    pass
            self._conns.clear()
        self._srv.close()


def follower_connect(host: str, port: int, process_id: int,
                     timeout: float = 120.0) -> socket.socket:
    deadline = timeout
    import time as _t

    t0 = _t.monotonic()
    while True:
        try:
            sock = socket.create_connection((host, port), timeout=5.0)
            break
        except OSError:
            if _t.monotonic() - t0 > deadline:
                raise
            _t.sleep(0.2)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(None)
    sock.sendall(_pack(process_id))
    return sock


# -- leader-side runner wrapper ----------------------------------------------

# Sentinel: "use your own copy" for device values that only exist
# process-locally (the logits a prefill call just produced). The follower's
# replayed prefill produced the bit-identical replicated value.
_PREV_LOGITS = "__prev_logits__"


_COLOCATED_ONLY = ("export_pages_device", "import_pages_device")
# what this class answers itself and never reads through to the wrapped
# runner: the colocated-only refusals and the run-ahead door (a handle
# names one process's arrays; the group replays whole steps)
_OWN = _COLOCATED_ONLY + ("can_run_ahead", "decode_dispatch", "decode_collect",
                          "mixed_dispatch", "mixed_collect")


class ReplicatingRunner(Runner):
    """Wraps the leader's ModelRunner: broadcast first, then execute
    locally. What is broadcast is what the declared door marks as a
    device step (engine/runner_api.DEVICE_STEPS: the methods that enqueue
    XLA programs or change device state, so that every process must run
    them); every other name (adapter_slot, kv_pool_bytes, pools_deleted,
    the facts...) is host-local and reads through to the wrapped runner.
    Device-array arguments cannot cross the wire — the only ones the
    engine passes are logits into sample_one, replaced by the
    _PREV_LOGITS sentinel (the follower substitutes its own replica)."""

    can_run_ahead = False  # followers replay decode_multi whole

    def __init__(self, runner, plane: StepPlaneLeader):
        self._runner = runner
        self._plane = plane

    def __getattribute__(self, name):
        # every public name belongs to the wrapped runner, the base's
        # defaults included: this class only stands in the door
        if name.startswith("_") or name in _OWN:
            return object.__getattribute__(self, name)
        attr = getattr(object.__getattribute__(self, "_runner"), name)
        if name not in DEVICE_STEPS:
            return attr
        plane = object.__getattribute__(self, "_plane")

        def call(*args, **kwargs):
            import jax

            wire_args = tuple(
                _PREV_LOGITS if isinstance(a, jax.Array) else a for a in args
            )
            plane.broadcast(name, wire_args, kwargs)
            return attr(*args, **kwargs)

        return call

    # device-handle paths are colocated-process-only by construction; a
    # multi-process group must use the host-staged wire format
    def export_pages_device(self, *a, **kw):
        raise RuntimeError("device-handle KV export is colocated-only; "
                           "multihost groups use export_pages()")

    def import_pages_device(self, *a, **kw):
        raise RuntimeError("device-handle KV import is colocated-only; "
                           "multihost groups use import_pages()")

    def decode_dispatch(self, *a, **kw):
        raise RuntimeError("a multihost group replays whole device steps: "
                           "decode_multi and decode_multi_with_prefills, not "
                           "their dispatch and collect halves")

    decode_collect = mixed_dispatch = mixed_collect = decode_dispatch


def follower_loop(runner, sock: socket.socket) -> None:
    """Replay the leader's call stream on this process's runner replica.
    Returns when the leader sends __stop__ or the connection drops."""
    last_logits = None
    while True:
        frame = _recv_frame(sock)
        if frame is None:
            log.warning("step plane: leader connection dropped")
            return
        method, args, kwargs = frame
        if method == "__stop__":
            log.info("step plane: leader stopped the group")
            return
        args = [last_logits if a == _PREV_LOGITS else a for a in args]
        try:
            out = getattr(runner, method)(*args, **kwargs)
        except Exception:
            # mirror the leader's per-request failure isolation
            # (engine.py catches step errors and keeps serving): a
            # follower that EXITS here would leave the leader's next
            # collective waiting on a dead rank forever. When the leader
            # hit the same exception the two stay in lockstep; a
            # follower-only failure shows up as divergent output, which
            # the group-parity tests exist to catch.
            log.exception("step plane: replay of %s failed; continuing", method)
            continue
        if method == "prefill":
            last_logits = out
        elif isinstance(out, MixedOut) and len(out[1]):
            last_logits = out[1][-1]  # a mixed step's chunks sample next


# -- worker-group entrypoint helpers -----------------------------------------


def _layout_guard_check(runner) -> str:
    """Run the strict layout guard over the live engine twice: once on
    the honest placement (must be clean) and once after seeding a spec
    drift — silently re-placing one sharded param replicated, exactly
    the implicit all-gather the guard exists to catch (must raise).
    Returns a deterministic signature string for the group-parity
    print."""
    import jax
    from jax.sharding import NamedSharding

    from dynamo_tpu.parallel.mesh import SPEC_REPLICATED
    from dynamo_tpu.runtime.sanitizer import Sanitizer, SanitizerViolation

    san = Sanitizer(strict=True, transfer_guard=False, warmup_steps=1)
    runner.attach_sanitizer(san)
    checked = san.check_layouts(runner)  # raises on any live mismatch
    drifted = jax.device_put(
        runner.params["layers"]["wq"],
        NamedSharding(runner.mesh, SPEC_REPLICATED),
    )
    drifted.block_until_ready()
    runner.params["layers"]["wq"] = drifted
    try:
        san.check_layouts(runner)
        caught = False
    except SanitizerViolation as e:
        caught = "layout" in str(e) and "wq" in str(e)
    return f"GUARD checked={checked} drift_caught={caught}"


def selftest_main(argv=None) -> None:
    """`python -m dynamo_tpu.parallel.multihost --process-id K --num N
    --coordinator H:P` — join an N-process group (1 virtual CPU device
    each), run prefill + fused decode on a TP=N tiny model, print the
    sampled tokens. All processes must print the identical line; the
    driver's dryrun spawns these to validate the multi-process mesh path
    without real multi-host hardware."""
    import argparse

    p = argparse.ArgumentParser("dynamo_tpu.parallel.multihost")
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--num", type=int, required=True)
    p.add_argument("--coordinator", required=True)
    p.add_argument("--axis", default="model", choices=["model", "pipe"],
                   help="mesh axis the group spans: TP (default) or "
                        "pipeline stages (GPipe serving path)")
    p.add_argument("--layout-guard", action="store_true",
                   help="after the serving flow, run the sanitizer's "
                        "layout guard over the live params/pools (must be "
                        "clean), then seed one spec drift and require the "
                        "guard to catch it as a hard violation")
    args = p.parse_args(argv)

    spec = MultihostSpec(
        coordinator=args.coordinator,
        num_processes=args.num,
        process_id=args.process_id,
        step_port=0,
        local_devices=1,
    )
    initialize(spec)

    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.config import get_config
    from dynamo_tpu.parallel.mesh import MeshConfig

    mesh = (MeshConfig(pipe=args.num) if args.axis == "pipe"
            else MeshConfig(model=args.num))
    runner = ModelRunner(
        get_config("tiny"), mesh,
        num_pages=32, page_size=4, max_pages_per_seq=8,
        decode_buckets=(1, 2, 4), prefill_buckets=(8, 16), seed=0,
    )
    s = {"temperature": [0.0], "top_k": [0], "top_p": [1.0], "seeds": [0]}
    logits = runner.prefill([1, 2, 3, 4, 5], 0, [0, 1, 2], prior_len=0)
    # plain path first (what every logprob-free request takes) ...
    tok0 = runner.sample_one(logits, s, 0)
    runner.decode_multi(2, [tok0], [5], [[0, 1, 2]], s, 1)
    if args.axis == "pipe":
        # each process is one GPipe stage; the sampling extras are not
        # wired on the PP path, so the group signature is the plain tokens
        out = runner.decode_multi(3, [tok0], [7], [[0, 1, 2]], s, 3)
        payload = runner.export_pages([0, 1])  # replicated-gather path
        runner.import_pages([3, 4], 0, payload)
        guard = f" {_layout_guard_check(runner)}" if args.layout_guard else ""
        print(f"MULTIHOST_SELFTEST pipe {[tok0] + out[0].tolist()}{guard}",
              flush=True)
        return
    # ... then the sampling extras (penalties + logprobs) — group replay
    # must cover the paths the engine takes whenever a request carries
    # logprobs/penalties
    tok, lp1 = runner.sample_one_ex(
        logits, s, 0, history=[1, 2, 3, 4, 5], n_logprobs=2
    )
    out, lp = runner.decode_multi(
        3, [tok], [7], [[0, 1, 2]], s, 3,
        n_logprobs=2, histories=[[1, 2, 3, 4, 5, tok]], prompt_lens=[5],
    )
    payload = runner.export_pages([0, 1])  # replicated-gather path
    runner.import_pages([3, 4], 0, payload)
    lp_sig = [round(float(lp1[0]), 4)] + [round(float(v), 4) for v in lp[0][0]]
    guard = f" {_layout_guard_check(runner)}" if args.layout_guard else ""
    print(f"MULTIHOST_SELFTEST {[tok] + out[0].tolist()} LP {lp_sig}{guard}",
          flush=True)


if __name__ == "__main__":
    selftest_main()
