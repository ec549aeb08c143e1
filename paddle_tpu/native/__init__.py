"""Native (C++) runtime components, built on demand with g++.

The reference keeps its data pipeline, trainers, and serving shells in C++
(SURVEY.md §2.1); this package holds their TPU-native equivalents compiled
as C-ABI shared libraries bound via ctypes (no pybind11 in this image).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_DIR, "_build")
_LOCK = threading.Lock()
_LIBS = {}


def _sources_digest(srcs, extra_flags) -> str:
    h = hashlib.sha256(" ".join(extra_flags).encode())
    for s in srcs:
        with open(s, "rb") as f:
            h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


def build_library(name: str, sources, extra_flags=()) -> str:
    """Compile sources into _build/lib<name>.so if stale; returns path.

    Stale means the sources' CONTENT (and flags) differ from what the
    library was built from — a sha256 stamp beside the .so, not mtimes:
    ``_build/`` is ignored by git but copied between machines as it lies
    on disk, and a copy rewrites every mtime."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    out = os.path.join(_BUILD_DIR, f"lib{name}.so")
    stamp = out + ".sha256"
    srcs = [os.path.join(_DIR, s) for s in sources]
    digest = _sources_digest(srcs, extra_flags)
    if os.path.exists(out) and os.path.exists(stamp):
        with open(stamp) as f:
            if f.read().strip() == digest:
                return out
    # compile to a temp name, then atomic-rename: a concurrent process must
    # never dlopen a half-written .so (the stamp lands after the library,
    # so a reader that sees the new stamp sees the new library)
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           *extra_flags, *srcs, "-o", tmp]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed: {' '.join(cmd)}\n"
                           f"{proc.stderr}")
    os.replace(tmp, out)
    with open(tmp, "w") as f:
        f.write(digest + "\n")
    os.replace(tmp, stamp)
    return out


def load_library(name: str, sources, extra_flags=()) -> ctypes.CDLL:
    with _LOCK:
        if name not in _LIBS:
            _LIBS[name] = ctypes.CDLL(
                build_library(name, sources, extra_flags))
        return _LIBS[name]
