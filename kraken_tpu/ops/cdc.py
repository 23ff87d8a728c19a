"""FastCDC content-defined chunking with the rolling-hash pass on TPU.

Absent from the reference (SURVEY.md SS2.6 table): this is north-star new
capability (BASELINE.json config #4) -- chunk Docker layers on content-
defined boundaries so identical file content shifted by tar offsets still
dedupes across layers.

Algorithm (the framework's normative spec; the pure-Python
:func:`chunk_reference` below is the golden oracle for tests):

- 32-bit gear rolling hash: ``h_i = (h_{i-1} << 1) + GEAR[b_i]  (mod 2^32)``.
  Because of the shift, ``h_i`` depends only on the last 32 bytes -- which is
  what makes the TPU pass possible: every position's hash is a *windowed*
  function, so all positions evaluate in parallel as 32 shifted adds over
  the gather ``GEAR[data]``.
- FastCDC normalized chunking: below the average chunk size a *strict* mask
  must hit (fewer cuts), above it a *loose* mask (more cuts); hard
  ``min_size``/``max_size`` bounds. Masks spread bits per the FastCDC paper
  style; here: contiguous high bits of the 32-bit hash.

Two-phase split (SURVEY.md SS7 hard part #4): the TPU computes the rolling
hash and both mask tests for *every* offset in one vector pass (the O(bytes)
work); the host then walks the resulting sparse candidate list applying the
sequential min/avg/max cut policy (O(cuts) work, ~bytes/avg_size items).
The phases compose to exactly the sequential algorithm because the cut
policy never looks at hashes, only candidate positions -- proven against
``chunk_reference`` in tests/test_cdc.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from kraken_tpu.core.hasher import device_section
from kraken_tpu.ops import next_pow2

_WINDOW = 32  # bytes of history in a 32-bit gear hash

# Deterministic gear function: framework constant, must never change (chunk
# boundaries are a persistent on-disk contract once dedup metadata is
# written). Defined ARITHMETICALLY (murmur-style avalanche of the byte)
# rather than as a lookup table: TPUs have no fast arbitrary gather -- a
# 256-entry table lookup ran the device pass at ~0.1 GB/s, while the same
# dispersion as 6 vector ops runs at memory speed. The table form below is
# derived from the function and is only used by host-side code.
_GEAR_C1 = 0x9E3779B1  # golden-ratio odd constant
_GEAR_C2 = 0x85EBCA77  # murmur3-style mixer


def _gear_fn_py(b: int) -> int:
    """Reference arithmetic gear: byte -> well-dispersed uint32."""
    x = ((b + 1) * _GEAR_C1) & 0xFFFFFFFF
    x ^= x >> 15
    x = (x * _GEAR_C2) & 0xFFFFFFFF
    x ^= x >> 13
    return x


GEAR = np.array([_gear_fn_py(i) for i in range(256)], dtype=np.uint32)


@dataclasses.dataclass(frozen=True)
class CDCParams:
    """Chunking parameters. ``avg_size`` must be a power of two."""

    min_size: int = 16 * 1024
    avg_size: int = 64 * 1024
    max_size: int = 256 * 1024
    # Normalization level: strict mask has (log2(avg) + nc) bits, loose has
    # (log2(avg) - nc). nc=2 per the FastCDC paper's recommendation.
    norm: int = 2

    def __post_init__(self):
        if self.avg_size & (self.avg_size - 1):
            raise ValueError(f"avg_size must be a power of two: {self.avg_size}")
        if not self.min_size <= self.avg_size <= self.max_size:
            raise ValueError("require min_size <= avg_size <= max_size")
        if self.min_size < _WINDOW:
            # Below this the vectorized pass (full 32-byte history at every
            # offset) and the sequential reference (hash restarts per chunk)
            # could disagree near chunk starts.
            raise ValueError(f"min_size must be >= {_WINDOW}: {self.min_size}")

    @property
    def bits(self) -> int:
        return self.avg_size.bit_length() - 1

    @property
    def mask_strict(self) -> int:
        return _top_mask(self.bits + self.norm)

    @property
    def mask_loose(self) -> int:
        return _top_mask(self.bits - self.norm)


def _top_mask(nbits: int) -> int:
    """A mask of ``nbits`` high bits of a uint32."""
    nbits = max(0, min(32, nbits))
    return ((1 << nbits) - 1) << (32 - nbits) & 0xFFFFFFFF


# -- pure-Python reference (golden oracle; O(n) python -- tests only) -------


def chunk_reference(data: bytes, params: CDCParams = CDCParams()) -> list[int]:
    """Sequential FastCDC. Returns chunk end offsets (exclusive)."""
    cuts = []
    n = len(data)
    start = 0
    while start < n:
        end = _next_cut_reference(data, start, n, params)
        cuts.append(end)
        start = end
    return cuts


def _next_cut_reference(data: bytes, start: int, n: int, p: CDCParams) -> int:
    remaining = n - start
    if remaining <= p.min_size:
        return n
    h = 0
    limit = min(remaining, p.max_size)
    norm_point = min(p.avg_size, limit)
    # Hash accumulates from the chunk start (matching the vector pass, which
    # has full history; the first min_size bytes are hashed but uncuttable).
    for i in range(limit):
        h = ((h << 1) + int(GEAR[data[start + i]])) & 0xFFFFFFFF
        if i + 1 <= p.min_size:
            continue
        mask = p.mask_strict if i + 1 <= norm_point else p.mask_loose
        if (h & mask) == 0:
            return start + i + 1
    return start + limit


# -- TPU vector pass --------------------------------------------------------


def _gear_fn_vec(b_u32: jax.Array) -> jax.Array:
    """Vectorized arithmetic gear (exactly :func:`_gear_fn_py`)."""
    x = (b_u32 + np.uint32(1)) * np.uint32(_GEAR_C1)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(_GEAR_C2)
    return x ^ (x >> np.uint32(13))


@functools.partial(jax.jit, static_argnames=("mask_s", "mask_l"))
def _gear_candidates(data_u8: jax.Array, mask_s: int, mask_l: int):
    """Rolling gear hash at every offset + both mask tests.

    data_u8: [L] uint8. Returns (strict, loose): [L] bool arrays where
    ``strict[i]`` means the hash of the 32-byte window ending at ``i``
    (inclusive) hits the strict mask.

    The windowed form: h_i = sum_{j=0..31} gear(b_{i-j}) << j -- a 32-tap
    correlation with weights 2^j. Evaluated by LOG-DOUBLING in 5 steps
    instead of 31 shifted adds: after step k every position holds its
    last-2^k-term partial sum H_k[i] = sum_{j<2^k} g[i-j] << j, and
    H_{k+1}[i] = H_k[i] + (H_k[i - 2^k] << 2^k). Same uint32 wraparound
    arithmetic, 6x fewer strided passes; measured 4.9 -> 9.8 GB/s/chip on
    v5e (2x -- the remaining cost is the per-step buffer materialization,
    not op count; PERF.md).
    """
    g = _gear_fn_vec(data_u8.astype(jnp.uint32))  # [L] uint32
    n = g.shape[0]
    h = jnp.concatenate([jnp.zeros(_WINDOW - 1, dtype=jnp.uint32), g])
    step = 1
    while step < _WINDOW:
        shifted = jnp.concatenate(
            [jnp.zeros(step, dtype=jnp.uint32), h[:-step]]
        )
        h = h + (shifted << np.uint32(step))
        step *= 2
    h = h[_WINDOW - 1 :]
    strict = (h & np.uint32(mask_s)) == 0
    loose = (h & np.uint32(mask_l)) == 0
    return strict, loose


def _host_select_cuts(
    strict_idx: np.ndarray, loose_idx: np.ndarray, n: int, p: CDCParams
) -> list[int]:
    """Sequential cut selection over sparse candidate positions.

    ``strict_idx``/``loose_idx`` hold positions i where the mask hit; a cut
    at position i ends a chunk at offset i+1. Equivalence with the
    sequential reference holds because candidates are only taken at offsets
    > min_size >= _WINDOW past the chunk start, where the 32-byte gear
    window lies entirely inside the current chunk -- so the full-history
    hash of the vector pass equals the restarted hash of the reference.
    """
    cuts: list[int] = []
    start = 0
    while start < n:
        remaining = n - start
        if remaining <= p.min_size:
            cuts.append(n)
            break
        limit = min(remaining, p.max_size)
        norm_point = min(p.avg_size, limit)
        # strict zone: offsets (start+min_size, start+norm_point]
        lo = np.searchsorted(strict_idx, start + p.min_size)
        hi = np.searchsorted(strict_idx, start + norm_point - 1, side="right")
        if lo < hi:
            end = int(strict_idx[lo]) + 1
        else:
            # loose zone: offsets (start+norm_point, start+limit]
            lo = np.searchsorted(loose_idx, start + norm_point)
            hi = np.searchsorted(loose_idx, start + limit - 1, side="right")
            end = int(loose_idx[lo]) + 1 if lo < hi else start + limit
        cuts.append(end)
        start = end
    return cuts


# Large blobs run the vector pass in fixed-size segments: the gear hash at
# position i depends only on bytes [i-31, i], so segments with a 31-byte
# left overlap produce bit-identical candidates to one whole-blob pass
# while bounding device/host memory to O(segment) (the u32 intermediates
# are 4-8x the byte count -- a whole-blob pass on a 10 GiB layer would
# materialize tens of GB).
_SEGMENT = 4 * 1024 * 1024


def cdc_section(kernel: str, rows: int, row_bytes: int, useful_bytes: int):
    """A device section of the chunking plane: a block is 64 bytes of a
    dispatched row, ``useful_bytes`` what of the dispatch is real input."""
    return device_section(
        "cdc", kernel, rows=rows, blocks=-(-row_bytes // 64),
        useful_blocks=-(-useful_bytes // 64), payload_bytes=useful_bytes,
    )


def _candidate_indices(
    arr: np.ndarray, n: int, params: CDCParams
) -> tuple[np.ndarray, np.ndarray]:
    """Global strict/loose candidate positions over ``arr[:n]``."""
    if n > _SEGMENT and jax.devices()[0].platform == "tpu":
        # TPU + enough bytes to amortize: the Pallas kernel (VMEM-
        # resident doubling, ~43 GB/s/chip chained vs ~10 for the XLA
        # path on v5e; bit-identical candidates). Allowlist on the
        # DEVICE platform (like parallel/hashplane.py's
        # mesh.devices.flat[0].platform): non-TPU accelerators (gpu,
        # neuron, ...) -- where the pltpu BlockSpecs cannot lower --
        # fall through to XLA.
        from kraken_tpu.ops.cdc_pallas import candidate_indices_pallas

        return candidate_indices_pallas(
            arr, n, params.mask_strict, params.mask_loose
        )
    if n <= _SEGMENT:
        # Small blobs: bucket to the next power of two (bounded jit cache).
        # Zero-pad bytes cannot create in-range candidates because only
        # positions < n are kept.
        padded = next_pow2(n)
        if padded != n:
            arr = np.concatenate([arr[:n], np.zeros(padded - n, dtype=np.uint8)])
        else:
            # Copy: jnp.asarray on CPU may alias the numpy buffer and
            # release it asynchronously; callers hand us mmap-backed views
            # whose close() must not race a device transfer (BufferError).
            arr = np.array(arr[:n], copy=True)
        with cdc_section("gear_candidates", 1, padded, n):
            strict, loose = _gear_candidates(
                jnp.asarray(arr), params.mask_strict, params.mask_loose
            )
            strict, loose = np.asarray(strict), np.asarray(loose)
        return np.flatnonzero(strict[:n]), np.flatnonzero(loose[:n])
    buf_len = _SEGMENT + _WINDOW - 1  # one fixed jit shape for every segment
    strict_parts: list[np.ndarray] = []
    loose_parts: list[np.ndarray] = []
    buf = np.zeros(buf_len, dtype=np.uint8)
    for s in range(0, n, _SEGMENT):
        lo = max(0, s - (_WINDOW - 1))
        seg = arr[lo : min(s + _SEGMENT, n)]
        buf[: len(seg)] = seg
        buf[len(seg) :] = 0
        with cdc_section("gear_candidates", 1, buf_len, len(seg)):
            strict, loose = _gear_candidates(
                jnp.asarray(buf), params.mask_strict, params.mask_loose
            )
            strict, loose = np.asarray(strict), np.asarray(loose)
        local = slice(s - lo, len(seg))  # valid, non-overlap positions
        strict_parts.append(np.flatnonzero(strict[local]) + s)
        loose_parts.append(np.flatnonzero(loose[local]) + s)
    return np.concatenate(strict_parts), np.concatenate(loose_parts)


def chunk(data: bytes | memoryview, params: CDCParams = CDCParams()) -> list[int]:
    """Content-defined chunk boundaries (end offsets, exclusive).

    TPU vector pass for the hashes (segmented: O(segment) memory for any
    blob size) + host scan for the cut policy; exactly equal to
    :func:`chunk_reference`.
    """
    view = memoryview(data)
    n = len(view)
    if n == 0:
        return []
    arr = np.frombuffer(view, dtype=np.uint8)
    strict_idx, loose_idx = _candidate_indices(arr, n, params)
    return _host_select_cuts(strict_idx, loose_idx, n, params)


def spans_from_cuts(cuts) -> list[tuple[int, int]]:
    """Cut end-offsets (exclusive, ascending) -> (start, end) spans."""
    spans = []
    start = 0
    for end in cuts:
        spans.append((start, int(end)))
        start = int(end)
    return spans


def chunk_spans(
    data: bytes | memoryview, params: CDCParams = CDCParams()
) -> list[tuple[int, int]]:
    """(start, end) spans for each chunk."""
    return spans_from_cuts(chunk(data, params))


def chunk_host(
    data: bytes | memoryview | np.ndarray, params: CDCParams = CDCParams()
) -> np.ndarray:
    """Host-plane chunker: cut end-offsets WITHOUT touching the device.

    For streaming workloads where the bytes never visit the chip (origin
    dedup scans over backend reads, the 100+ GB corpus bench): the native
    C chunker when built (~1.5 GB/s/core), else a NumPy evaluation of the
    same windowed-gear candidates + the shared host cut policy. Both are
    bit-identical to :func:`chunk_reference` (tests/test_native.py,
    tests/test_cdc.py)."""
    arr = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data
    n = arr.size
    if n == 0:
        return np.empty(0, dtype=np.uint64)
    from kraken_tpu.native import cdc_chunk_native

    cuts = cdc_chunk_native(
        arr, params.min_size, params.avg_size, params.max_size,
        params.mask_strict, params.mask_loose,
    )
    if cuts is not None:
        return cuts
    # NumPy fallback: the same h_i = sum_j gear(b_{i-j}) << j windowed
    # form as the device pass (uint32 wraparound matches the sequential
    # (h << 1) + gear accumulation for positions with full 32-byte
    # history -- the only positions the cut policy may select past
    # min_size). SEGMENTED with a 31-byte overlap like _candidate_indices:
    # the u32 intermediates are 8x the byte count, and a whole-buffer
    # pass on a 10 GiB layer would materialize ~80 GB.
    strict_parts: list[np.ndarray] = []
    loose_parts: list[np.ndarray] = []
    ms = np.uint32(params.mask_strict)
    ml = np.uint32(params.mask_loose)
    for s in range(0, n, _SEGMENT):
        lo = max(0, s - (_WINDOW - 1))
        seg = arr[lo : min(s + _SEGMENT, n)]
        g = GEAR[seg]
        # Same log-doubling as the device paths: 5 shifted adds, not 31.
        h = g.copy()
        step = 1
        while step < min(_WINDOW, len(seg)):
            h[step:] += h[:-step].copy() << np.uint32(step)
            step *= 2
        local = h[s - lo :]
        strict_parts.append(np.flatnonzero((local & ms) == 0) + s)
        loose_parts.append(np.flatnonzero((local & ml) == 0) + s)
    return np.asarray(
        _host_select_cuts(
            np.concatenate(strict_parts), np.concatenate(loose_parts),
            n, params,
        ),
        dtype=np.uint64,
    )
