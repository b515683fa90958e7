"""On-demand build + ctypes loader for the native host-side kernels.

The reference's host-side native layer (Spark JVM shuffle machinery, Arrow
C++) is replaced by small C++ kernels compiled here with g++ on first use
into ``native/build/`` (git-ignored: never part of a checkout). A library
is loaded only if it was built from the current ``.cc`` with the current
flags ON THIS MACHINE — the file name carries a digest of all three — so a
build directory that arrived by copying a working tree is rebuilt, not
trusted (``-march=native`` code from another CPU is a SIGILL waiting).

Callers fall back to NumPy implementations with identical semantics when a
library cannot be built, but never silently: the failure is warned once
with the compiler's output, counted (``native.build_failed``) and kept in
``status()``, which ``chip_smoke.py`` checks.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import threading
import warnings
from typing import Dict, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "build")
_CXX = ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17"]
_LOCK = threading.RLock()
_LIBS: dict = {}
#: name -> None (loaded) | why it could not be built or loaded
_STATUS: Dict[str, Optional[str]] = {}


def _machine_id() -> str:
    """What "this machine" means for a ``-march=native`` binary: the
    running boot of this host (a copied tree lands on another boot)."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        return platform.node()


def _so_path(name: str, src: str) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX).encode())
    h.update(_machine_id().encode())
    return os.path.join(_BUILD_DIR, f"lib{name}-{h.hexdigest()[:16]}.so")


def status() -> Dict[str, Optional[str]]:
    """name -> None (loaded) | the error, for every library asked for."""
    with _LOCK:
        return dict(_STATUS)


def load_library(name: str) -> Optional[ctypes.CDLL]:
    """Compile (if needed) and load ``native/<name>.cc``; None on failure
    (warned, counted and recorded in ``status()``)."""
    with _LOCK:
        if name in _STATUS:
            return _LIBS.get(name)
        src = os.path.join(_HERE, f"{name}.cc")
        try:
            so = _so_path(name, src)
            if not os.path.exists(so):
                os.makedirs(_BUILD_DIR, exist_ok=True)
                for stale in glob.glob(
                        os.path.join(_BUILD_DIR, f"lib{name}-*.so")):
                    # `so` itself: a racing process's, just finished; one
                    # already gone: a racing process removed it first
                    if stale != so:
                        with contextlib.suppress(FileNotFoundError):
                            os.unlink(stale)
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(_CXX + [src, "-o", tmp], check=True,
                               capture_output=True, text=True, timeout=120)
                os.replace(tmp, so)  # atomic: a racing process sees all or none
            _LIBS[name] = ctypes.CDLL(so)
            _STATUS[name] = None
        except (OSError, subprocess.SubprocessError) as e:
            why = f"{type(e).__name__}: {e}"
            stderr = getattr(e, "stderr", None)
            if stderr:
                why += "\n" + stderr.strip()[-2000:]
            _STATUS[name] = why
            from ..utils.profiler import PROFILER
            PROFILER.count("native.build_failed")
            warnings.warn(
                f"sml_tpu native library {name!r} unavailable, using the "
                f"NumPy implementation: {why}", RuntimeWarning, stacklevel=2)
        return _LIBS.get(name)
