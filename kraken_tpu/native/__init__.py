"""Native host-side components (C, built on first use, ctypes-bound).

The reference keeps its hot loops in Go on the host; here the chip does
the hashing and the host's only hot job is FEEDING it (SURVEY.md SS7 hard
part #2).  This package holds those feeder kernels.  No pybind11 in the
image -- plain ctypes over a cc-compiled shared object, with a NumPy
fallback when no toolchain is available.
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
_SRC = os.path.join(_HERE, "hostpack.c")
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
    an exception. The build also takes no ``-march=native``: the AVX-512
    packer is selected at run time by ``__builtin_cpu_supports``."""
    cc = shutil.which("cc") or shutil.which("gcc") or shutil.which("clang")
    if cc is None:
        return None
    with open(_SRC, "rb") as f:
        key = hashlib.sha256(f.read() + b"\0" + _host_identity())
    out = os.path.join(_HERE, f"_hostpack-{key.hexdigest()[:16]}.so")
    if os.path.exists(out):
        return out
    # Build into a temp file then atomically rename: concurrent importers
    # (test workers, herd processes) must never load a half-written .so.
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(
            [cc, "-O3", "-shared", "-fPIC", "-pthread", _SRC, "-o", tmp],
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
            "host chunker/packer: NumPy fallback (no C compiler, or the "
            "build failed)",
            extra={"impl": "numpy"},
        )
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.kt_pack_tiles_mt.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_size_t,
        ]
        lib.kt_pack_tiles_mt.restype = None
        lib.kt_pack_tiles_range.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_size_t,
            ctypes.c_size_t,
        ]
        lib.kt_pack_tiles_range.restype = None
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
            "host chunker/packer: C library %s", path, extra={"impl": "c"}
        )
    except (OSError, AttributeError) as e:
        _LIB = None
        _log.warning(
            "host chunker/packer: NumPy fallback (%s failed to load: %s)",
            path, e, extra={"impl": "numpy"},
        )
    return _LIB


def have_native_packer() -> bool:
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


def default_pack_threads() -> int:
    """Feeder thread count: all cores (the pack is memory-bound, L1-blocked,
    and embarrassingly parallel over 16-piece groups), overridable via
    ``KT_PACK_THREADS``."""
    env = os.environ.get("KT_PACK_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass  # malformed override: ignore, use the core count
    return max(1, os.cpu_count() or 1)


def _check_pack_args(
    data: np.ndarray, nb_out: int, out: np.ndarray | None
) -> tuple[np.ndarray, np.ndarray, int, int, int]:
    """Contiguity/dtype/size assertions shared by every pack entry point.

    The C packer takes raw pointers: a strided view, a wrong dtype, or an
    undersized ``out`` (a bufpool lease cut too small, the ingest plane's
    staging hazard) would silently corrupt memory at AVX store rates.
    Validated HERE, once, so the GIL-free pack loops stay branch-free."""
    if data.dtype != np.uint8 or data.ndim != 2:
        raise ValueError(f"pack: need [M, piece_len] uint8, got "
                         f"{data.dtype}{list(data.shape)}")
    m, piece_len = data.shape
    if m % 1024 or piece_len % 64:
        raise ValueError("pack: need M % 1024 == 0 and piece_len % 64 == 0")
    nbd = piece_len // 64
    if nb_out < nbd:
        raise ValueError("pack: nb_out < piece blocks")
    t = m // 1024
    data = np.ascontiguousarray(data)
    if out is None:
        out = np.zeros((t, nb_out, 16, 1024), dtype=np.uint32)
    else:
        if out.dtype != np.uint32:
            raise ValueError(f"pack: out must be uint32, got {out.dtype}")
        if out.shape != (t, nb_out, 16, 1024):
            raise ValueError(
                f"pack: out shape {out.shape} != {(t, nb_out, 16, 1024)}"
            )
        if not out.flags["C_CONTIGUOUS"] or not out.flags["WRITEABLE"]:
            raise ValueError("pack: out must be C-contiguous and writable")
    return data, out, m, piece_len, t


def pack_tiles(
    data: np.ndarray,
    nb_out: int,
    out: np.ndarray | None = None,
    threads: int | None = None,
) -> np.ndarray:
    """Pack [M, piece_len] uint8 pieces (M % 1024 == 0, piece_len % 64 == 0)
    into the kernel's word-major [T, nb_out, 16, 8*128] big-endian u32
    layout.  Uses the C packer (multi-threaded over 16-piece groups) when
    available, NumPy otherwise."""
    data, out, m, piece_len, t = _check_pack_args(data, nb_out, out)
    nbd = piece_len // 64
    lib = _load()
    if lib is not None:
        lib.kt_pack_tiles_mt(
            data.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            m,
            piece_len,
            nb_out,
            default_pack_threads() if threads is None else max(1, threads),
        )
        return out
    # NumPy fallback: same layout, ~10x slower.
    w = data.reshape(t, 1024, nbd, 16, 4)
    be = (
        (w[..., 0].astype(np.uint32) << 24)
        | (w[..., 1].astype(np.uint32) << 16)
        | (w[..., 2].astype(np.uint32) << 8)
        | w[..., 3].astype(np.uint32)
    )  # [t, 1024, nbd, 16]
    out[:, :nbd] = be.transpose(0, 2, 3, 1)
    return out


def pack_tiles_range(
    data: np.ndarray,
    nb_out: int,
    out: np.ndarray,
    g_lo: int,
    g_hi: int,
) -> None:
    """Pack ONLY 16-piece groups ``[g_lo, g_hi)`` of ``data`` into ``out``
    on the calling thread -- the cooperative entry HashPool pack workers
    use: ctypes releases the GIL for the duration of the C call, so N
    workers packing disjoint ranges of one window scale with cores.
    Bounds are clamped to the group count; ``out`` must be the
    caller-zeroed full destination (ranges only write their own stripes).
    Requires the native library (callers check :func:`have_native_packer`
    and fall back to :func:`pack_tiles`)."""
    data, out, m, piece_len, _ = _check_pack_args(data, nb_out, out)
    lib = _load()
    if lib is None or not hasattr(lib, "kt_pack_tiles_range"):
        raise RuntimeError("pack_tiles_range: native packer unavailable")
    lib.kt_pack_tiles_range(
        data.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        m,
        piece_len,
        nb_out,
        max(0, g_lo),
        max(0, g_hi),
    )


def pack_tiles_pooled(
    data: np.ndarray, nb_out: int, pool, out: np.ndarray | None = None
) -> np.ndarray:
    """Pack one window through ``pool`` (a core.hasher.HashPool): the
    group range splits across the pool's workers via ``run_sharded``,
    each worker packing its contiguous stripe GIL-free through
    :func:`pack_tiles_range`. Falls back to the single-call path when the
    native library (or a multi-worker pool) is absent."""
    data, out, m, piece_len, _ = _check_pack_args(data, nb_out, out)
    if (
        pool is None
        or pool.workers < 2
        or not have_native_packer()
        or not hasattr(_LIB, "kt_pack_tiles_range")
    ):
        return pack_tiles(
            data, nb_out, out=out,
            threads=pool.workers if pool is not None else None,
        )
    n_groups = m // 16

    def worker(lo: int, hi: int) -> None:
        pack_tiles_range(data, nb_out, out, lo, hi)

    pool.run_sharded(n_groups, worker)
    return out
