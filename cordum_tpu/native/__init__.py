"""Native (C) acceleration for control-plane hot loops.

Builds lazily with the system compiler on first use and loads via ctypes
(no pybind11 in the image); every consumer has a pure-Python fallback, so
the framework works without a toolchain.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

from ..infra import logging as logx

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "strategy_scan.c")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _lib_path() -> str:
    """Output path stamped with the source hash.

    Binaries are never committed; the library is only loaded if its name
    matches the current source's hash, so a stale artifact (from a previous
    source revision) can never be silently loaded into the scheduler hot path.
    """
    with open(_SRC, "rb") as f:
        h = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libstrategy_scan-{h}.so")


def _build(out: str) -> bool:
    # several processes may build at once (a fresh checkout under parallel
    # test workers, a stack of service binaries): each compiles to a name
    # of its own and renames it into place, so no one loads a half-written
    # library
    tmp = f"{out}.{os.getpid()}.tmp"
    for cc in ("cc", "gcc", "clang"):
        try:
            subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
                check=True, capture_output=True, timeout=60,
            )
            os.replace(tmp, out)
            return True
        except (FileNotFoundError, subprocess.CalledProcessError, subprocess.TimeoutExpired):
            continue
    return False


def load_strategy_scan() -> Optional[ctypes.CDLL]:
    """The compiled scan library, or None (callers fall back to Python)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    try:
        lib_file = _lib_path()
        if not os.path.exists(lib_file):
            import glob

            for stale in glob.glob(os.path.join(_DIR, "libstrategy_scan-*.so")):
                if stale == lib_file:
                    continue  # another process built it meanwhile
                try:
                    os.unlink(stale)  # drop artifacts of older source revisions
                except OSError:
                    pass
            if not _build(lib_file):
                logx.warn("native strategy scan unavailable (no C compiler)")
                return None
        lib = ctypes.CDLL(lib_file)
        lib.pick_worker.restype = ctypes.c_int32
        lib.pick_worker.argtypes = [
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint64),   # cap_bits
            ctypes.POINTER(ctypes.c_int32),    # pool_id
            ctypes.POINTER(ctypes.c_int32),    # topology_id
            ctypes.POINTER(ctypes.c_int32),    # chip_count
            ctypes.POINTER(ctypes.c_float),    # active_jobs
            ctypes.POINTER(ctypes.c_float),    # max_parallel
            ctypes.POINTER(ctypes.c_float),    # cpu_load
            ctypes.POINTER(ctypes.c_float),    # duty_cycle
            ctypes.POINTER(ctypes.c_uint8),    # healthy
            ctypes.c_uint64,                   # req_caps
            ctypes.POINTER(ctypes.c_int32),    # allowed_pools
            ctypes.c_int32,                    # n_pools
            ctypes.c_int32,                    # min_chips
            ctypes.c_int32,                    # req_topology_id
        ]
        _lib = lib
        logx.info("native strategy scan loaded", lib=lib_file)
    except OSError as e:
        logx.warn("native strategy scan failed to load", err=str(e))
        _lib = None
    return _lib
