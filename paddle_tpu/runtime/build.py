"""Builds csrc/ into libpaddle_tpu_rt-<digest>.so on first use, named by a
digest of the csrc/ sources: a library built from other sources has another
name and cannot load, whatever mtimes a copy of the tree gave it.

The reference ships its native runtime as CMake targets; here the library is
small enough that a single g++ invocation at import keeps the source tree the
only build input. Set PADDLE_TPU_NO_NATIVE=1 to skip (pure-Python fallbacks
are used where they exist)."""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CSRC = os.path.join(_REPO, "csrc")
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_lib")


def _so_path() -> str:
    digest = hashlib.sha256()
    for fn in sorted(os.listdir(CSRC)):
        if fn.endswith((".cc", ".h")):
            digest.update(fn.encode() + b"\0")
            with open(os.path.join(CSRC, fn), "rb") as f:
                digest.update(f.read())
    return os.path.join(
        OUT_DIR, f"libpaddle_tpu_rt-{digest.hexdigest()[:16]}.so"
    )


def ensure_built(verbose: bool = False) -> Optional[str]:
    """Compile if needed; returns the .so path or None when unavailable."""
    if os.environ.get("PADDLE_TPU_NO_NATIVE"):
        return None
    if not os.path.isdir(CSRC):
        return None
    so_path = _so_path()
    if os.path.exists(so_path):
        return so_path
    os.makedirs(OUT_DIR, exist_ok=True)
    sources = sorted(
        os.path.join(CSRC, f) for f in os.listdir(CSRC) if f.endswith(".cc")
    )
    tmp = so_path + f".tmp.{os.getpid()}"
    cmd = [
        "g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread",
        "-o", tmp, *sources,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        if verbose:
            print(f"native build unavailable: {e}", file=sys.stderr)
        return None
    if proc.returncode != 0:
        if verbose:
            print(f"native build failed:\n{proc.stderr}", file=sys.stderr)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    os.replace(tmp, so_path)
    return so_path
