"""Build-on-demand for native components: compiles native/*.cpp into
shared libraries under native/build/, named by a hash of the source and
the compile command — so only what git would commit decides which
artefact is loaded (a stale or foreign `lib*.so` left on disk is never
picked up)."""

from __future__ import annotations

import hashlib
import logging
import os
import subprocess
from pathlib import Path
from typing import Optional

log = logging.getLogger("dynamo_tpu.native")

NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
BUILD_DIR = NATIVE_DIR / "build"


def build_library(name: str, cxxflags: Optional[list] = None) -> Optional[Path]:
    """Compile native/{name}.cpp → native/build/lib{name}.<sha>.so; returns
    the path, or None if the toolchain is unavailable or compilation fails
    (callers fall back to their Python implementation and `native_report`
    says so)."""
    src = NATIVE_DIR / f"{name}.cpp"
    if not src.exists():
        return None
    flags = ["-O3", "-std=c++17", "-shared", "-fPIC", *(cxxflags or [])]
    key = hashlib.sha256(
        src.read_bytes() + b"\0" + " ".join(flags).encode()
    ).hexdigest()[:16]
    out = BUILD_DIR / f"lib{name}.{key}.so"
    if out.exists():
        return out
    BUILD_DIR.mkdir(parents=True, exist_ok=True)
    # build under a private name, then rename: a concurrent process must
    # never dlopen a half-written library
    tmp = BUILD_DIR / f".lib{name}.{key}.{os.getpid()}.tmp"
    cmd = ["g++", *flags, str(src), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        log.info("built native library %s", out)
        return out
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired, FileNotFoundError) as e:
        stderr = getattr(e, "stderr", b"") or b""
        log.warning("native build of %s failed (%s); using Python fallback",
                    name, stderr.decode(errors="replace")[:500])
        tmp.unlink(missing_ok=True)
        return None


def native_report() -> dict:
    """Which implementation each native component resolved to in this
    process: "c++" or "python" (chip_smoke.py fails on "python" where a
    g++ exists — a silent fallback would hide a broken build)."""
    from dynamo_tpu.native import block_index, frame_codec

    return {
        "block_index": "c++" if block_index.available() else "python",
        "frame_codec": "c++" if frame_codec.available() else "python",
    }
