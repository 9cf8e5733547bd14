"""Cross-process host-memory weight staging — the TPU answer to the
reference's gpu_memory_service (lib/gpu_memory_service/README.md:1-40).

The reference keeps weights resident in a GPU-memory service so a
restarting worker re-attaches via CUDA IPC handles instead of reloading
from disk. TPUs expose no cross-process device-memory handles, so the
TPU-first equivalent stages the HOST copy in POSIX shared memory
(/dev/shm): the first worker on a host publishes the flattened param
tree once; every peer — SO_REUSEPORT tier members, DP replicas on the
same host, crash-restarted workers — attaches zero-copy read-only numpy
views and device_puts straight out of the mapping. No disk read, no
per-process host duplicate of a multi-GB tree, and the staging survives
the death of the process that created it (segments are detached from
Python's resource tracker exactly so worker crashes don't tear the
cache down).

Commit protocol: ONE segment per stage, written under a per-pid temp
name and os.rename()d into place — atomic on tmpfs, so an attacher can
only ever observe a COMPLETE stage (there is no torn half-published
state to detect or repair, the failure mode heuristic grace periods
exist for). A publisher that dies mid-write leaves only its temp file,
which later publishers garbage-collect by checking the embedded pid is
dead. publish() REPLACES any existing stage (weight-version rollover and
stale-model recovery are both just "publish again"); attachers that
opened the old inode keep their complete mapping until they close it.

Segment layout: [u64 BE index length][msgpack index {version, meta,
entries: [(path, shape, dtype, offset, nbytes)], total}][padding]
[64-byte-aligned array bytes...]. `meta` is caller-owned (the worker
stores a model-config fingerprint and refuses a stage whose fingerprint
disagrees — sharing a stage name across different models is recovered,
not crashed on).

Pairs with the persistent XLA compilation cache
(dynamo_tpu.enable_compilation_cache): together a warm restart skips both
recompiles and weight I/O. Linux-only by construction (tmpfs rename); on hosts without
/dev/shm the tier reports unavailable and workers load cold.
"""

from __future__ import annotations

import logging
import os
import struct
import time
from multiprocessing import shared_memory
from typing import Any, Dict, Optional

import msgpack
import numpy as np

log = logging.getLogger("dynamo_tpu.shm_weights")

VERSION = 2
_ALIGN = 64
_HDR = struct.Struct(">Q")
SHM_DIR = "/dev/shm"


def _seg_name(name: str) -> str:
    safe = "".join(c if c.isalnum() or c in "-_" else "_" for c in name)
    return f"dynshm_{safe}"


def available() -> bool:
    return os.path.isdir(SHM_DIR)


def _keep_after_exit(shm: shared_memory.SharedMemory) -> None:
    """Detach the segment from the resource tracker: staging must outlive
    the creating worker (the whole point — a crashed worker's successor
    attaches instead of reloading). Cleanup is explicit via unlink() or
    replacement by a later publish."""
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        # tracker internals shifted — staging still works, it just dies
        # with the creator on this Python
        log.debug("resource_tracker unregister failed for %s", shm._name,
                  exc_info=True)


def _flatten(params: Any):
    import jax

    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        leaves.append((key, np.asarray(leaf)))
    return leaves


def _unflatten(entries: Dict[str, np.ndarray]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for key, arr in entries.items():
        node = tree
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return tree


def _gc_temp_segments(seg: str) -> None:
    """Remove temp files abandoned by dead publishers (name carries the
    writer's pid; a live writer's temp is never touched)."""
    prefix = f"{seg}.p"
    try:
        names = os.listdir(SHM_DIR)
    except OSError:
        return
    for n in names:
        if not n.startswith(prefix):
            continue
        try:
            pid = int(n[len(prefix):])
        except ValueError:
            continue
        if not os.path.exists(f"/proc/{pid}"):
            try:
                os.unlink(os.path.join(SHM_DIR, n))
                log.info("collected abandoned shm temp %s (pid %d dead)",
                         n, pid)
            except OSError:
                pass


def publish(name: str, params: Any, meta: Optional[Dict[str, Any]] = None) -> bool:
    """Stage `params` (pytree of host arrays) under `name`, REPLACING any
    existing stage atomically (rename commit). Returns False only when
    shared memory is unavailable on this host."""
    if not available():
        log.warning("%s missing: shm weight staging disabled", SHM_DIR)
        return False
    # N co-hosted workers cold-booting concurrently would otherwise each
    # write a full temp copy into tmpfs (transient N x multi-GB): when a
    # fingerprinted stage equal to ours already exists, staging is done —
    # skip the copy entirely
    if meta:
        existing = attach(name)
        if existing is not None:
            same = existing.meta == meta
            existing.close()
            if same:
                return False
    seg = _seg_name(name)
    _gc_temp_segments(seg)
    leaves = _flatten(params)
    entries = []
    blob_guess = msgpack.packb(
        {"version": VERSION, "meta": meta or {}, "total": 0,
         "entries": [(k, list(a.shape), str(a.dtype), 0, a.nbytes)
                     for k, a in leaves]},
        use_bin_type=True,
    )
    # data starts after header+index, aligned; offsets are absolute.
    # The guess packed every offset and total as 0 (1 msgpack byte);
    # the real values re-pack into at most 9 bytes each — reserve that
    # growth for ONE offset per leaf plus the total field.
    base = (_HDR.size + len(blob_guess) + 9 * (len(leaves) + 1)
            + _ALIGN - 1) // _ALIGN * _ALIGN
    off = base
    for key, arr in leaves:
        off = (off + _ALIGN - 1) // _ALIGN * _ALIGN
        entries.append((key, list(arr.shape), str(arr.dtype), off, arr.nbytes))
        off += arr.nbytes
    total = max(off, _HDR.size + 1)
    blob = msgpack.packb(
        {"version": VERSION, "meta": meta or {}, "total": total,
         "entries": entries},
        use_bin_type=True,
    )
    assert _HDR.size + len(blob) <= base, "index overran reserved space"

    tmp = f"{seg}.p{os.getpid()}"
    try:
        shm = shared_memory.SharedMemory(name=tmp, create=True, size=total)
    except FileExistsError:
        # our own pid's leftover from a previous interrupted publish
        os.unlink(os.path.join(SHM_DIR, tmp))
        shm = shared_memory.SharedMemory(name=tmp, create=True, size=total)
    try:
        _keep_after_exit(shm)
        shm.buf[: _HDR.size] = _HDR.pack(len(blob))
        shm.buf[_HDR.size : _HDR.size + len(blob)] = blob
        for (key, arr), (_, _, _, o, nb) in zip(leaves, entries):
            dst = np.ndarray(arr.shape, dtype=arr.dtype, buffer=shm.buf,
                             offset=o)
            dst[...] = arr
        # the commit: atomic on tmpfs — attachers only ever see either
        # the previous complete stage or this complete one
        os.rename(os.path.join(SHM_DIR, tmp), os.path.join(SHM_DIR, seg))
        log.info("staged %d arrays (%.1f MB) in shm as %s",
                 len(entries), total / 1e6, name)
        return True
    except BaseException:
        try:
            os.unlink(os.path.join(SHM_DIR, tmp))
        except OSError:
            pass
        raise
    finally:
        shm.close()


class Stage:
    """An attached stage: `params` is a pytree of zero-copy READ-ONLY
    numpy views into shared memory; `meta` is the publisher's fingerprint
    dict. Keep this object alive while the views are in use (it pins the
    mapping — even across a replacing publish, which swaps the name to a
    new inode without disturbing this one)."""

    def __init__(self, shm: shared_memory.SharedMemory, params: Any,
                 meta: Dict[str, Any], n_arrays: int, nbytes: int):
        self._shm = shm
        self.params = params
        self.meta = meta
        self.n_arrays = n_arrays
        self.nbytes = nbytes

    def close(self) -> None:
        self.params = None
        self._shm.close()


def attach(name: str, wait_s: float = 0.0) -> Optional[Stage]:
    """Attach to a published stage; None when absent or unparseable
    (a corrupt segment — e.g. hand-created bytes under our name — is
    logged and treated as absent; the next publish replaces it)."""
    if not available():
        return None
    seg = _seg_name(name)
    deadline = time.monotonic() + wait_s
    while True:
        try:
            shm = shared_memory.SharedMemory(name=seg)
            # CPython < 3.13 registers ATTACH-side handles with the
            # resource tracker too, which unlinks "leaked" segments at
            # interpreter exit — i.e. the first attacher to exit would
            # destroy the stage for every other worker. Detach it.
            _keep_after_exit(shm)
            break
        except FileNotFoundError:
            if time.monotonic() >= deadline:
                return None
            time.sleep(0.1)
    try:
        (blob_len,) = _HDR.unpack(bytes(shm.buf[: _HDR.size]))
        meta = msgpack.unpackb(
            bytes(shm.buf[_HDR.size : _HDR.size + blob_len]), raw=False
        )
        if not isinstance(meta, dict) or meta.get("version") != VERSION:
            raise ValueError(f"version {meta.get('version')!r}"
                             if isinstance(meta, dict) else "not a map")
        import ml_dtypes

        entries: Dict[str, np.ndarray] = {}
        for key, shape, dtype, off, _nb in meta["entries"]:
            dt = (np.dtype(ml_dtypes.bfloat16) if "bfloat16" in dtype
                  else np.dtype(dtype))
            arr = np.ndarray(tuple(shape), dtype=dt, buffer=shm.buf,
                             offset=off)
            # the mapping is shared by every co-hosted worker: an
            # in-place write would corrupt the weights for all of them —
            # make that an immediate local ValueError
            arr.flags.writeable = False
            entries[key] = arr
    except Exception as e:
        log.warning("shm stage %s unreadable (%s); treating as absent",
                    name, e)
        shm.close()
        return None
    return Stage(shm, _unflatten(entries), meta.get("meta") or {},
                 len(entries), meta["total"])


def unlink(name: str) -> None:
    """Explicitly remove a stage (shutdown cleanup; weight rollover needs
    no unlink — publish replaces atomically)."""
    try:
        os.unlink(os.path.join(SHM_DIR, _seg_name(name)))
    except OSError:
        pass
    _gc_temp_segments(_seg_name(name))
