"""Native (C++) runtime components.

The reference delegates its native heavy lifting to external JVM systems
(Spark, HBase, Postgres — SURVEY.md §2); here the TPU compute path is
XLA and the host-side IO plane is C++ compiled on first use:

  eventlog.cpp  append-only event journal (CRC-framed, flock-safe) backing
                the EVLOG storage driver

`load(name)` compiles `<name>.cpp` with g++ into `_build/` on first use
and returns a ctypes handle; callers must handle `None` (no toolchain)
with a pure-Python fallback so the framework never hard-requires a
compiler at runtime. Nothing built is committed: a checkout holds the
sources only.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

_DIR = Path(__file__).resolve().parent
_BUILD = _DIR / "_build"
_lock = threading.Lock()
_cache = {}


def load(name: str) -> Optional[ctypes.CDLL]:
    """Build (if this source was never built) and dlopen
    native/<name>.cpp; None on failure. The shared object is named by
    the source's content hash — a copied checkout has arbitrary mtimes,
    so staleness is decided by content, never by time."""
    with _lock:
        if name in _cache:
            return _cache[name]
        src = _DIR / f"{name}.cpp"
        lib = None
        try:
            digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
            so = _BUILD / f"lib{name}-{digest}.so"
            if not so.exists():
                _BUILD.mkdir(exist_ok=True)
                # build beside the target, then rename: a concurrent
                # process never dlopens a half-written object
                tmp = so.with_suffix(f".{os.getpid()}.tmp")
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-o", str(tmp),
                     str(src)],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, so)
            lib = ctypes.CDLL(str(so))
        except (OSError, subprocess.SubprocessError):
            lib = None
        _cache[name] = lib
        return lib
