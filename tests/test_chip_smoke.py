"""chip_smoke.py's contract off the chip: without a TPU it fails and prints
no result, and its parent process never imports jax (a chip belongs to one
process at a time — the children need it). The end-to-end rehearsal on the
CPU is `slow`."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRIVE = r"""
import sys
import chip_smoke
rc = chip_smoke.main(sys.argv[1:])
leaked = [m for m in ("jax", "jaxlib", "dynamo_tpu") if m in sys.modules]
print("PARENT_IMPORTS", leaked, file=sys.stderr)
sys.exit(rc)
"""


def _run(*argv, timeout):
    return subprocess.run(
        [sys.executable, "-c", _DRIVE, *argv], cwd=REPO, timeout=timeout,
        capture_output=True, text=True,
    )


def test_chip_smoke_fails_without_tpu_and_parent_stays_off_jax():
    out = _run(timeout=300)
    assert out.returncode != 0, out.stdout[-2000:]
    assert "PARENT_IMPORTS []" in out.stderr, out.stderr[-2000:]
    # JAX itself refused the backend in the first JAX child, and no result
    # line was printed
    assert "Unable to initialize backend 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.slow
def test_chip_smoke_rehearsal_end_to_end():
    out = _run("--rehearse", timeout=1500)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "PARENT_IMPORTS []" in out.stderr
    assert out.stdout.rstrip().endswith("REHEARSAL passed — not a chip result")
    assert '"ok": true' not in out.stdout
