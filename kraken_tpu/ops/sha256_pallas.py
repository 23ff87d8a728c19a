"""Pallas TPU kernels for batched SHA-256 -- the tuned metainfo-gen path.

Why a kernel (SURVEY.md SS7 hard part #1): the portable XLA scan in
:mod:`kraken_tpu.ops.sha256` pays a loop-iteration overhead per 64-byte
block (the carry bounces through HBM and every iteration is a separate
fused-kernel launch), which caps throughput far below the VPU's integer
rate. Here the whole block chain runs inside one ``pallas_call``:

- grid = (piece_tiles, block_groups). Pallas revisits the same output
  block for every ``b`` step of a tile, so the running [8, N] hash state
  lives in VMEM for the whole chain -- written back to HBM once per tile.
- within one compression the 48 schedule extensions + 64 rounds are fully
  unrolled straight-line vector ops on [N]-wide uint32 lanes (N=1024 = a
  full 8x128 VPU tile per op; ``_rounds64``, ~3.4k equations). Unlike
  XLA:CPU, Mosaic compiles the body without pathological simplification
  passes.
- the blocks of a grid step are a ROLLED loop (``jax.lax.fori_loop``) over
  message words parked in a VMEM scratch, in both kernels: a shape traces
  ONE compression (~5.9k equations with the relayout), not one a block.
  What a shape costs a start is Python tracing and lowering under the
  interpreter lock, which no compile cache holds: with the uniform
  kernel's eight blocks and its padding block unrolled (nine copies,
  ~32k equations) that was 7.1-8.0 s a shape on an idle chip machine;
  rolled it is 1.2-1.5 s (PR 34).
- the message schedule runs as a 16-word ring (w[i+16] computed in place
  right after round i consumes w[i]), keeping ~24 vector registers live
  instead of 72 -- a fully materialized 64-entry schedule spills.

All parallelism is cross-piece: SHA-256's chain serializes blocks within a
piece, so pieces are the batch axis and the block axis is the grid's inner
sequential dimension.

Two kernels, both on [8, 128] lanes with the same rounds (``_rounds64``);
a block below is one 64-byte step of every lane of a tile, v5e:

| kernel | takes | measured |
|---|---|---|
| ``sha256_tiles`` | equal-length rows, natural bytes; chain length and padding block compiled in (a Mosaic compile per length) | block loop rolled (PR 34, 2026-10-04, idle chip): one 4 MiB piece a dispatch 65.8 ms = 1.00 us a block; a 16 x 4 MiB window 64.2 ms = 0.98 us a block, 76.9 ms = 1.17 with the copy in and the read back; 8 x 8 MiB and 4 x 16 MiB 0.97 and 0.98; a full tile of 1 MiB rows 17.1 ms = 1.04 us a block (~63 GB/s); a shape's first use 1.7-2.4 s (trace 0.9-1.2, lower 0.3, Mosaic 0.4-0.8 cold). Unrolled, same day and chip: 0.94, 0.92 (1.13), 0.91, 0.93 and 0.98 us a block, first use 8.4-9.3 s; ~75 GB/s a full tile = 0.87 us a block (r3, 2026-07-29) |
| ``sha256_ragged_slab`` (``sha256_ragged_tiles`` drives it) | SHA-padded rows of any lengths, a block count a lane, the state carried from call to call: two compiled shapes for every length and row count | one row, 512 blocks a call: 1.01-1.09 us a block from 1 to 4 MiB, copy, dispatch and read-back included, and ~1.5 ms a chain before the first block; a tile of 1024 rows, 64 blocks a call: 9.1-9.6 us a block, bound by the copy of 64 KiB a block (PR 26, 2026-10-01; the XLA scan it replaced: 199-211 us a block at one row, 30 at 8-16) |

The uniform kernel's input layout (docs/PERF_HISTORY.md has the measured
analysis, v5e 2026-07-29) is the **natural** ``[M, piece_len] uint8`` the
store hands over. The kernel transposes each [N_TILE, _KB*64] BYTE slab in
VMEM (u8 granularity) and recombines the four byte planes into big-endian
words with vector shifts -- the BE combine is the byteswap, for free.
**~75 GB/s/chip** measured (median of repeated runs, r3, the block loop
unrolled; a tile of 1,024 x 1 MiB on PR 34's chip: 67 GB/s unrolled, 63
rolled). The round-2
u32-word transpose managed only ~18: Mosaic's 32-bit transpose was the
binding constraint; the u8 transpose of the same bytes runs ~4x faster and
the u16 variant sits between (~22). Older alternatives -- per-sublane-group
square transposes (14), MXU byte-plane transpose via identity matmul
(13.8), XLA pre-transpose (10.7), two-pass repack kernel (15.6) -- all
slower still.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kraken_tpu.ops.sha256 import _H0, _K, _pad_block_for

# Pieces per grid tile, laid out as an explicit (sublane, lane) = (8, 128)
# VPU tile so every round op maps to whole vector registers. VMEM per grid
# step: in block N*KB*64 = 512 KiB (x2 double buffer) + state 32 KiB + the
# parked words' scratch (KB+1)*16*N*4 = 576 KiB (512 in the ragged kernel).
_SUB = 8
_LANES = 128
N_TILE = _SUB * _LANES
# Blocks folded per grid step: amortizes per-step pipeline overhead (the
# block chain is ~16k steps/tile for 4 MiB pieces if KB=1). Swept 8/16/32
# on v5e: flat at ~18 GB/s for the natural path; 8 keeps VMEM small.
_KB = 8


def _rotr(x, n):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _bswap32(x):
    """LE device word -> BE SHA word (vector shifts; ~6 VPU ops)."""
    return (
        ((x & np.uint32(0xFF)) << np.uint32(24))
        | ((x & np.uint32(0xFF00)) << np.uint32(8))
        | ((x >> np.uint32(8)) & np.uint32(0xFF00))
        | (x >> np.uint32(24))
    )


def _rounds64(state, wget):
    """One SHA-256 compression (fully unrolled, 16-word schedule ring).

    ``state``: list of 8 [_SUB, _LANES] uint32 tiles; ``wget(j)`` returns
    message word j as a tile. Returns the post-feed-forward state.
    """
    a, b, c, d, e, f, g, h = state
    w = [wget(j) for j in range(16)]
    for i in range(64):
        wi = w[i % 16]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        # ch/maj in their 3-op/4-op forms (vs the textbook 4/5).
        # Measured neutral on v5e -- Mosaic strength-reduces the textbook
        # forms -- kept because fewer ops can't hurt other backends.
        ch = g ^ (e & (f ^ g))
        t1 = h + s1 + ch + np.uint32(_K[i]) + wi
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & (b ^ c)) ^ (b & c)
        a, b, c, d, e, f, g, h = t1 + s0 + maj, a, b, c, d + t1, e, f, g
        if i < 48:
            w15 = w[(i + 1) % 16]
            w2 = w[(i + 14) % 16]
            e0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
            e1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
            w[i % 16] = wi + e0 + w[(i + 9) % 16] + e1
    return [s + v for s, v in zip(state, (a, b, c, d, e, f, g, h))]


def _park_words(blk_ref, w_ref):
    """Relayout a natural [N_TILE, _KB*64] uint8 slab into w_ref's first
    _KB*16 [_SUB, _LANES] big-endian message words, block-major.

    Piece-major -> word-major as ONE up-front BYTE transpose. Granularity
    matters enormously on v5e (measured r3, same kernel otherwise): u8
    transpose ~68 GB/s end-to-end, u16 ~22, u32 ~18. Recombining the four
    byte planes into big-endian words costs 3 shifts + 3 ors per word and
    IS the byteswap -- the LE->BE conversion falls out of plane order.
    The words are parked in VMEM so that the block loop over them can be
    ROLLED: a kernel traces one compression instead of one a block, a
    fifth of the Python tracing and of Mosaic's work on every start.
    """
    t8 = jnp.transpose(blk_ref[...], (1, 0)).reshape(
        _KB, 16, 4, _SUB, _LANES
    )
    for kb in range(_KB):
        for j in range(16):
            b0 = t8[kb, j, 0].astype(jnp.uint32)
            b1 = t8[kb, j, 1].astype(jnp.uint32)
            b2 = t8[kb, j, 2].astype(jnp.uint32)
            b3 = t8[kb, j, 3].astype(jnp.uint32)
            w_ref[kb * 16 + j] = (
                (b0 << np.uint32(24))
                | (b1 << np.uint32(16))
                | (b2 << np.uint32(8))
                | b3
            )


def _make_kernel(nb_real: int, pad_words: np.ndarray):
    """Grid-step kernel for a chain of ``nb_real`` data blocks.

    The shared SHA padding block is compile-time constants
    (``pad_words``) -- it never exists in HBM: the chain's last group
    writes it into the scratch right after its last real block and the
    one rolled loop folds it with them. blk_ref is a natural
    [N_TILE, _KB*64] uint8 BYTE slab; out_ref: [1, 8, _SUB, _LANES],
    revisited across the block-group axis (carries the running state in
    VMEM); w_ref: [(_KB+1)*16, _SUB, _LANES] uint32 scratch, the group's
    message words and room for the padding block after a full group.
    """
    ngroups = (nb_real + _KB - 1) // _KB
    last_real = nb_real - (ngroups - 1) * _KB  # blocks of the last group

    def kernel(blk_ref, out_ref, w_ref):
        b = pl.program_id(1)
        last = b == ngroups - 1

        @pl.when(b == 0)
        def _init():
            for i in range(8):
                out_ref[0, i] = jnp.full((_SUB, _LANES), _H0[i], jnp.uint32)

        _park_words(blk_ref, w_ref)

        @pl.when(last)
        def _park_pad():
            # Right after the last real block: over what the jnp.pad of a
            # ragged block axis left, or in the spare slots after a full group.
            for j in range(16):
                w_ref[last_real * 16 + j] = jnp.full(
                    (_SUB, _LANES), np.uint32(pad_words[j]), jnp.uint32
                )

        def block(kb, state):
            return tuple(_rounds64(list(state), lambda j: w_ref[kb * 16 + j]))

        state = jax.lax.fori_loop(
            0, jnp.where(last, last_real + 1, _KB), block,
            tuple(out_ref[0, i] for i in range(8)),
        )
        for i in range(8):
            out_ref[0, i] = state[i]

    return kernel


def _resolve_interpret(interpret: bool | None) -> bool:
    # interpret=None picks interpret mode iff the default backend is CPU;
    # pass it explicitly when placing the call on a non-default platform
    # (e.g. a virtual CPU mesh while a real TPU is attached).
    return jax.default_backend() == "cpu" if interpret is None else interpret


@functools.partial(jax.jit, static_argnames=("unpadded_blocks", "interpret"))
def sha256_tiles(
    data_u8: jax.Array,
    pad_block: jax.Array,
    unpadded_blocks: int,
    interpret: bool | None = None,
):
    """Hash equal-length pieces from the NATURAL layout.

    data_u8: [M, P] uint8, any M >= 1, P = unpadded_blocks * 64;
    pad_block: [16] uint32 shared SHA padding block (kept for API
    stability; the kernel folds compile-time constants). Returns [M, 8]
    uint32 digest words.

    A batch shorter than a tile is NOT padded to N_TILE rows: the last
    tile's block simply overhangs the array, and the lanes past row M
    hash whatever the edge block holds and are sliced off. Shipped
    batches are 4-16 rows of 4-16 MiB; padding them to the tile cost
    1024 x piece_length of device memory per dispatch.
    """
    interpret = _resolve_interpret(interpret)
    m = data_u8.shape[0]
    t = pl.cdiv(m, N_TILE)
    nb = unpadded_blocks
    ngroups = (nb + _KB - 1) // _KB

    # Natural piece-major BYTE slabs, one _KB-block group per grid step --
    # no XLA-side data movement (an XLA pre-transpose was the v1
    # bottleneck: ~12 GB/s); the kernel does the u8 relayout in VMEM.
    if nb % _KB:
        # Pad the block axis so the final grid group has a real slab to
        # DMA; the kernel's loop ends before it folds the content.
        data_u8 = jnp.pad(data_u8, ((0, 0), (0, (ngroups * _KB - nb) * 64)))

    pad_words = np.asarray(_pad_block_for(nb * 64), dtype=np.uint32)

    out = pl.pallas_call(
        _make_kernel(nb, pad_words),
        interpret=interpret,
        grid=(t, ngroups),
        in_specs=[
            pl.BlockSpec(
                (N_TILE, _KB * 64), lambda ti, bi: (ti, bi),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, 8, _SUB, _LANES), lambda ti, bi: (ti, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((t, 8, _SUB, _LANES), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM(((_KB + 1) * 16, _SUB, _LANES), jnp.uint32)
        ],
    )(data_u8)
    return out.reshape(t, 8, N_TILE).transpose(0, 2, 1).reshape(-1, 8)[:m]


# -- ragged rows: any length, any row count, one compiled shape ------------
#
# sha256_tiles bakes the chain length and the padding block into the
# kernel, so every distinct length is a Mosaic compile (~9 s on the v5e
# plus the Python tracing). Short and odd-length rows -- blobs under a
# piece, tails, CDC chunks -- cannot pay that, and the XLA scan they used
# instead launches ~170 device ops for every 64-byte block. The ragged
# kernel takes rows the host has already SHA-padded, a block count for
# every lane and the running state as an input AND an output: a chain of
# any length is a sequence of calls of ONE fixed extent over successive
# slabs of the block axis. Its compile key is (lanes, slab): neither the
# row count nor the length.

# The two shapes the served path dispatches: one row a call (a blob or a
# tail is a chain of its own; nothing but its own bytes crosses to the
# device), or a whole tile of 1024 rows (CDC chunks of a large blob; rows
# the batch lacks ride as uninitialised lanes with a block count of 0).
# (lanes, blocks a call): 32 KiB of one row, or 4 KiB of each of 1024.
RAGGED_ROW_SHAPE = (1, 512)
RAGGED_TILE_SHAPE = (N_TILE, 64)


def _ragged_kernel(scal_ref, nblk_ref, blk_ref, state_ref, out_ref, w_ref):
    """One _KB-block group of one slab. scal_ref: SMEM [2] int32 = (chain
    index of the slab's first block, longest lane's block count);
    nblk_ref: [_SUB, _LANES] int32 block count per lane; blk_ref: natural
    [N_TILE, _KB*64] uint8 slab (rows past the array's ride the edge block
    as in sha256_tiles); state_ref/out_ref: [8, _SUB, _LANES] uint32, one
    HBM buffer (aliased), out_ref revisited across the grid so the running
    state stays in VMEM; w_ref: [_KB*16, _SUB, _LANES] uint32 scratch."""
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _load():
        out_ref[...] = state_ref[...]

    first = scal_ref[0] + g * _KB

    # A group past the longest lane's chain does nothing: the block axis
    # is rounded up to the slab, and a 1 KiB row pays for 3 groups of it.
    @pl.when(first < scal_ref[1])
    def _fold():
        _park_words(blk_ref, w_ref)
        nblk = nblk_ref[...]

        def block(kb, state):
            new = _rounds64(list(state), lambda j: w_ref[kb * 16 + j])
            # A lane past its own count keeps its state.
            live = first + kb < nblk
            return tuple(jnp.where(live, n, s) for n, s in zip(new, state))

        state = jax.lax.fori_loop(
            0, _KB, block, tuple(out_ref[i] for i in range(8))
        )
        for i in range(8):
            out_ref[i] = state[i]


@functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnames=("state",)
)
def sha256_ragged_slab(
    state: jax.Array,
    data_u8: jax.Array,
    nblocks: jax.Array,
    scalars: jax.Array,
    interpret: bool | None = None,
) -> jax.Array:
    """Fold one slab of the block axis into the running state.

    state: [8, _SUB, _LANES] uint32 (donated; lane r = row r); data_u8:
    [R, S*64] uint8, R <= N_TILE SHA-padded rows' bytes for chain blocks
    [first, first + S), S a multiple of _KB; nblocks: [_SUB, _LANES] int32
    per-lane block count (0 for a lane with no row); scalars: [2] int32 =
    (first, max(nblocks)). Returns the new state.
    """
    interpret = _resolve_interpret(interpret)
    groups = data_u8.shape[1] // (_KB * 64)
    return pl.pallas_call(
        _ragged_kernel,
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups,),
            in_specs=[
                pl.BlockSpec(
                    (_SUB, _LANES), lambda g, s: (0, 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (N_TILE, _KB * 64), lambda g, s: (0, g),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (8, _SUB, _LANES), lambda g, s: (0, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (8, _SUB, _LANES), lambda g, s: (0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.VMEM((_KB * 16, _SUB, _LANES), jnp.uint32)],
        ),
        out_shape=jax.ShapeDtypeStruct((8, _SUB, _LANES), jnp.uint32),
        input_output_aliases={3: 0},
    )(scalars, nblocks, data_u8, state)


def sha256_ragged_tiles(
    rows_u8: np.ndarray,
    nblocks: np.ndarray,
    shape: tuple[int, int],
    interpret: bool | None = None,
) -> np.ndarray:
    """Hash n <= lanes SHA-padded rows of any lengths as one chain of slab
    calls of the compiled ``shape`` = (lanes, slab blocks).

    rows_u8: [n, B*64] uint8 host array, each row SHA-padded (0x80, zeros,
    bit length) and zero- or garbage-filled past its own blocks, B a
    multiple of the slab; nblocks: [n] block count per row. Every slab is
    enqueued without waiting; the one wait is the read of the final state.
    Returns [n, 8] uint32 digest words.
    """
    lanes, slab = shape
    n, width = rows_u8.shape
    assert 0 < n <= lanes and width % (slab * 64) == 0
    longest = int(nblocks.max())
    per_lane = np.zeros(N_TILE, dtype=np.int32)
    per_lane[:n] = nblocks
    nblk = jax.device_put(per_lane.reshape(_SUB, _LANES))
    state = np.repeat(_H0, N_TILE).reshape(8, _SUB, _LANES)
    for first in range(0, longest, slab):
        data = rows_u8[:, first * 64 : (first + slab) * 64]
        if n < lanes:
            # Lanes without a row are never read (count 0): no memset.
            tile = np.empty((lanes, slab * 64), dtype=np.uint8)
            tile[:n] = data
            data = tile
        state = sha256_ragged_slab(
            state, data, nblk, np.array([first, longest], dtype=np.int32),
            interpret=interpret,
        )
    return np.asarray(state).reshape(8, N_TILE).T[:n]


def hash_pieces_device(
    data_u8: jax.Array, piece_length: int, interpret: bool | None = None
) -> jax.Array:
    """Device-resident uniform-piece hashing from the natural layout.

    data_u8: [M, piece_length] uint8, any M >= 1 (a short batch rides
    the last tile's edge block -- see :func:`sha256_tiles`); returns
    [M, 8] uint32 digest words. piece_length must be a multiple of 64.
    """
    if piece_length % 64:
        raise ValueError("pallas path requires piece_length % 64 == 0")
    pad = jnp.asarray(_pad_block_for(piece_length))
    return sha256_tiles(data_u8, pad, piece_length // 64, interpret=interpret)
