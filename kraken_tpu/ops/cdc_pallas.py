"""Pallas TPU kernel for the FastCDC gear pass: VMEM-resident doubling.

The XLA evaluation of the windowed gear sum (ops/cdc.py
``_gear_candidates``) round-trips every doubling step through HBM --
~40 B of HBM traffic per input byte -- capping it at ~10 GB/s/chip. This
kernel keeps all five doubling steps in VMEM and measured
**~43 GB/s/chip** with the robust chained method (44-62 with the
jitter-exposed marginal method; either way ~4-5x the XLA path --
PERF.md), bit-identical output.

Layout: bytes ride as [rows, 128] lane tiles in flat row-major order, so
a flat shift by ``step < 128`` is a lane-concat of each row's head with
the previous row's tail -- two vector selects, no relayout through HBM.
Each grid step processes one ``_SEG``-byte segment whose first ``_LEAD``
lanes carry the previous segment's last 31 bytes (same overlap scheme as
the XLA path, so candidates are bit-identical to a whole-blob pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kraken_tpu.ops.cdc import _WINDOW, _gear_fn_vec, cdc_section

_SEG = 1 << 18          # data bytes per grid step (VMEM-bounded: u32
                        # intermediates are 4x, plus live doubling copies)
_LEAD = 1024            # lane-aligned left-overlap region (last 31 used)
_BUF = _SEG + _LEAD
_ROWS = _BUF // 128
_PAD = _WINDOW - 1
_T_DISPATCH = 256       # segments per pallas_call (64 MiB data, 1 jit
                        # entry; large groups amortize per-call overhead)


def _make_kernel(mask_s: int, mask_l: int, first_group: bool):
    def kernel(d_ref, s_ref, l_ref):
        g = _gear_fn_vec(d_ref[0].astype(jnp.uint32))  # [_ROWS, 128]
        # Padding lanes must contribute ZERO history in g-domain --
        # gear(0) != 0, so zero BYTES are not enough (the XLA path pads
        # with uint32 zeros after the gear map; matching it exactly is
        # the bit-identity contract). Real history in the lead region is
        # only its last 31 lanes -- and none at all in the blob's first
        # segment.
        flat = (
            jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 128), 0) * 128
            + jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 128), 1)
        )
        cut = jnp.where(
            (pl.program_id(0) == 0) if first_group else False,
            _LEAD, _LEAD - _PAD,
        )
        g = jnp.where(flat < cut, jnp.uint32(0), g)
        h = g
        step = 1
        while step < _WINDOW:
            prev = jnp.concatenate(
                [jnp.zeros((1, 128), jnp.uint32), h[:-1]], axis=0
            )
            shifted = jnp.concatenate(
                [prev[:, 128 - step:], h[:, : 128 - step]], axis=1
            )
            h = h + (shifted << np.uint32(step))
            step *= 2
        hv = h[_LEAD // 128 :]
        s_ref[0] = ((hv & np.uint32(mask_s)) == 0).astype(jnp.uint8)
        l_ref[0] = ((hv & np.uint32(mask_l)) == 0).astype(jnp.uint8)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("mask_s", "mask_l", "first_group", "interpret"),
)
def _gear_pallas(
    segs_u8, mask_s: int, mask_l: int,
    first_group: bool = False, interpret: bool = False,
):
    """segs_u8: [T, _ROWS, 128] uint8 -> (strict, loose) [T, _SEG/128, 128]
    uint8 masks. ``first_group``: this dispatch's segment 0 is the BLOB's
    first segment (its whole lead region is padding, not overlap)."""
    t = segs_u8.shape[0]
    return pl.pallas_call(
        _make_kernel(mask_s, mask_l, first_group),
        interpret=interpret,
        grid=(t,),
        in_specs=[
            pl.BlockSpec(
                (1, _ROWS, 128), lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=[
            pl.BlockSpec(
                (1, _SEG // 128, 128), lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, _SEG // 128, 128), lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, _SEG // 128, 128), jnp.uint8),
            jax.ShapeDtypeStruct((t, _SEG // 128, 128), jnp.uint8),
        ],
    )(segs_u8)


def candidate_indices_pallas(
    arr: np.ndarray, n: int, mask_s: int, mask_l: int,
    interpret: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Global strict/loose candidate positions over ``arr[:n]`` via the
    kernel. Drop-in for the XLA path's contract (zero history before
    offset 0; only positions < n returned)."""
    nseg = (n + _SEG - 1) // _SEG
    strict_parts: list[np.ndarray] = []
    loose_parts: list[np.ndarray] = []
    for group in range(0, nseg, _T_DISPATCH):
        t = min(_T_DISPATCH, nseg - group)
        # Dispatch size buckets to powers of two (bounded jit cache, same
        # trick as cdc.py's small-blob path): a 5 MiB blob must not pay a
        # fixed 64 MiB staging + transfer + fetch-back round.
        t_disp = 16
        while t_disp < t:
            t_disp *= 2
        segs = np.zeros((t_disp, _BUF), dtype=np.uint8)
        filled = 0
        for i in range(t):
            s = (group + i) * _SEG
            lo = max(0, s - _PAD)
            chunk = arr[lo : min(s + _SEG, n)]
            segs[i, _LEAD - (s - lo) : _LEAD - (s - lo) + len(chunk)] = chunk
            filled += len(chunk)
        with cdc_section("gear_pallas", t_disp, _BUF, filled):
            strict, loose = _gear_pallas(
                jnp.asarray(segs.reshape(t_disp, _ROWS, 128)),
                mask_s, mask_l,
                first_group=(group == 0), interpret=interpret,
            )
            # Slice to live segments ON DEVICE: fetching the padded rows
            # back would double the D2H bytes for ragged tails.
            strict = np.asarray(strict[:t]).reshape(t, _SEG)
            loose = np.asarray(loose[:t]).reshape(t, _SEG)
        for i in range(t):
            s = (group + i) * _SEG
            valid = min(_SEG, n - s)
            strict_parts.append(np.flatnonzero(strict[i, :valid]) + s)
            loose_parts.append(np.flatnonzero(loose[i, :valid]) + s)
    return np.concatenate(strict_parts), np.concatenate(loose_parts)
