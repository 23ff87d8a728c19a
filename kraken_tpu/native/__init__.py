"""Native host-side components (C, built on first use, ctypes-bound).

The reference keeps its hot loops in Go on the host; here the chip does
the hashing and what is left for the host is the sequential FastCDC
chunker (``hostcdc.c``).  No pybind11 in the image -- plain ctypes over a
cc-compiled shared object, with a NumPy fallback (``ops/cdc.py``) when no
toolchain is available.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import shutil
import subprocess
import tempfile
from typing import Optional

import numpy as np

_log = logging.getLogger("kraken.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "hostcdc.c")
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _host_identity() -> bytes:
    """What tells one build host from another: architecture, host name
    and the CPU's feature flags."""
    flags = b""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith(b"flags"):
                    flags = line
                    break
    except OSError:
        pass
    return f"{platform.machine()}|{platform.node()}|".encode() + flags


def _build() -> Optional[str]:
    """Path of the shared object built from THIS source on THIS host,
    compiling it if absent. The name carries a hash of the source and of
    the host's identity, so an object that arrived with a copied tree
    (another source revision, another machine) is never picked up --
    loading one built for a different CPU is an illegal instruction, not
    an exception. The build also takes no ``-march=native``."""
    cc = shutil.which("cc") or shutil.which("gcc") or shutil.which("clang")
    if cc is None:
        return None
    with open(_SRC, "rb") as f:
        key = hashlib.sha256(f.read() + b"\0" + _host_identity())
    out = os.path.join(_HERE, f"_hostcdc-{key.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    # Build into a temp file then atomically rename: concurrent importers
    # (test workers, herd processes) must never load a half-written .so.
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(
            [cc, "-O3", "-shared", "-fPIC", _SRC, "-o", tmp],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, out)
        return out
    except (subprocess.CalledProcessError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    path = _build()
    if path is None:
        _log.info(
            "host chunker: NumPy fallback (no C compiler, or the "
            "build failed)",
            extra={"impl": "numpy"},
        )
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.kt_cdc_chunk.argtypes = [
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_uint32,
            ctypes.c_uint32,
            ctypes.c_void_p,
            ctypes.c_size_t,
        ]
        lib.kt_cdc_chunk.restype = ctypes.c_size_t
        _LIB = lib
        _log.info(
            "host chunker: C library %s", path, extra={"impl": "c"}
        )
    except (OSError, AttributeError) as e:
        _LIB = None
        _log.warning(
            "host chunker: NumPy fallback (%s failed to load: %s)",
            path, e, extra={"impl": "numpy"},
        )
    return _LIB


def have_native_chunker() -> bool:
    return _load() is not None


def cdc_chunk_native(
    data: np.ndarray,
    min_size: int,
    avg_size: int,
    max_size: int,
    mask_strict: int,
    mask_loose: int,
) -> Optional[np.ndarray]:
    """Sequential FastCDC cut offsets via the C chunker (~1.5 GB/s/core);
    None when no native library is available. ``data`` is a contiguous
    uint8 array; returns uint64 end offsets (exclusive)."""
    lib = _load()
    if lib is None or not hasattr(lib, "kt_cdc_chunk"):
        return None
    data = np.ascontiguousarray(data, dtype=np.uint8)
    n = data.size
    cap = n // min_size + 2
    cuts = np.empty(cap, dtype=np.uint64)
    ncuts = lib.kt_cdc_chunk(
        data.ctypes.data_as(ctypes.c_void_p),
        n,
        min_size,
        avg_size,
        max_size,
        mask_strict,
        mask_loose,
        cuts.ctypes.data_as(ctypes.c_void_p),
        cap,
    )
    return cuts[:ncuts]
