"""Batched SHA-256 on TPU -- the system's crypto hot loop, as one big vector op.

The reference hashes pieces one at a time on the CPU (uber/kraken
``lib/metainfogen`` generator loop and ``lib/torrent/storage`` piece verify
-- upstream paths, unverified; see SURVEY.md SS2.3/SS2.2). SHA-256's 64-round
dependency chain cannot be parallelized *within* a message, so the TPU win
comes entirely from the batch axis: thousands of pieces hashed in lockstep,
each round a [N]-wide uint32 vector op on the VPU (8x128 lanes).

Layout: a piece of L bytes is SHA-padded to B = (L+8)//64 + 1 blocks of 16
big-endian uint32 words. We stream pieces to the device as raw uint8 (no
host-side byteswap copy), pack to uint32 on device, and `lax.scan` the
compression function over the block axis with a [N, 8] state carry. Ragged
batches (pieces of different lengths) are handled by per-piece block counts
and masked state updates -- one dispatch, no recompiles per length.

Memory: 10k x 4 MiB pieces = 40 GB, far over HBM. ``hash_pieces`` streams
fixed-size sub-batches; JAX's async dispatch overlaps the host->device copy
of batch i+1 with the compute of batch i.

Shapes are bucketed (N and B rounded up to powers of two) so the jit cache
stays small across varying blob sizes.

That scan is the portable path: what runs on the CPU backend. On an
accelerator the same rows go to the Pallas kernels of
:mod:`kraken_tpu.ops.sha256_pallas` (``JaxPieceHasher`` chooses from
``jax.default_backend()``): full pieces to the uniform tile kernel, every
other row to the ragged tile kernel, because one scan iteration is some
170 device ops whatever it hashes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from kraken_tpu.core.hasher import (
    DIGEST_SIZE,
    PieceHasher,
    device_section,
    register_hasher,
    sha_blocks,
)
from kraken_tpu.core.hasher import record_hash_metrics as _record_hash_metrics
from kraken_tpu.ops import next_pow2 as _next_pow2

# fmt: off
_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)
_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)
# fmt: on


def _rotr(x: jax.Array, n: int) -> jax.Array:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


# Scan unroll factor: balances trace/compile size against loop overhead.
# A fully unrolled 64-round body (~1300 ops) sends XLA:CPU's algebraic
# simplifier into a multi-minute fixpoint loop; unroll=8 compiles in
# seconds on both CPU and TPU while keeping per-step vector work dense.
_UNROLL = 8


def _compress(state: jax.Array, block: jax.Array) -> jax.Array:
    """One SHA-256 compression: state [..., 8], block [..., 16] uint32.

    Both the message-schedule extension (48 steps, 16-word sliding carry)
    and the 64 rounds run as ``lax.scan`` so the compiled graph stays small;
    every step is [batch]-wide uint32 vector work on the VPU.
    """

    def sched_step(carry, _):
        # carry: [..., 16] = w[i-16 .. i-1]
        w15, w2 = carry[..., 1], carry[..., 14]
        s0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
        s1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
        new = carry[..., 0] + s0 + carry[..., 9] + s1
        return jnp.concatenate([carry[..., 1:], new[..., None]], axis=-1), new

    _, w_ext = jax.lax.scan(
        sched_step, block, None, length=48, unroll=_UNROLL
    )  # [48, ...]
    w_all = jnp.concatenate([jnp.moveaxis(block, -1, 0), w_ext], axis=0)  # [64, ...]

    def round_step(st, kw):
        k, w = kw
        a, b, c, d, e, f, g, h = st
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k + w
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        return (t1 + t2, a, b, c, d + t1, e, f, g), None

    st0 = tuple(state[..., i] for i in range(8))
    st, _ = jax.lax.scan(
        round_step, st0, (jnp.asarray(_K), w_all), unroll=_UNROLL
    )
    return jnp.stack([state[..., i] + st[i] for i in range(8)], axis=-1)


def _pack_be_u32(b: jax.Array) -> jax.Array:
    """[..., 4k] uint8 -> [..., k] uint32, big-endian (SHA byte order)."""
    b = b.astype(jnp.uint32).reshape(*b.shape[:-1], -1, 4)
    return (
        (b[..., 0] << np.uint32(24))
        | (b[..., 1] << np.uint32(16))
        | (b[..., 2] << np.uint32(8))
        | b[..., 3]
    )


@functools.partial(jax.jit, static_argnames=("unpadded_blocks",))
def _sha256_uniform(data_u8: jax.Array, pad_block: jax.Array, unpadded_blocks: int):
    """Hash N equal-length pieces whose length is a multiple of 64.

    data_u8: [N, P] uint8 with P = unpadded_blocks * 64; pad_block: [16]
    uint32 -- the shared final SHA padding block (0x80, zeros, bit length).
    Returns [N, 8] uint32 digest words.
    """
    n = data_u8.shape[0]
    blocks = data_u8.reshape(n, unpadded_blocks, 64)

    def body(state, blk_u8):
        return _compress(state, _pack_be_u32(blk_u8)), None

    state = jnp.broadcast_to(jnp.asarray(_H0), (n, 8))
    # scan over the block chain: carry is the [N, 8] running state.
    state, _ = jax.lax.scan(body, state, jnp.swapaxes(blocks, 0, 1))
    return _compress(state, jnp.broadcast_to(pad_block, (n, 16)))


@jax.jit
def _sha256_ragged(blocks_u8: jax.Array, nblocks: jax.Array):
    """Hash N pieces of varying block counts, pre-padded on host.

    blocks_u8: [N, B, 64] uint8 (SHA padding already applied per piece);
    nblocks: [N] int32 -- valid block count per piece. Blocks past a piece's
    count are skipped via masked state update. Returns [N, 8] uint32.
    """
    n = blocks_u8.shape[0]

    def body(state, x):
        i, blk_u8 = x
        new = _compress(state, _pack_be_u32(blk_u8))
        keep = (i < nblocks)[:, None]
        return jnp.where(keep, new, state), None

    state = jnp.broadcast_to(jnp.asarray(_H0), (n, 8))
    idx = jnp.arange(blocks_u8.shape[1], dtype=jnp.int32)
    state, _ = jax.lax.scan(body, state, (idx, jnp.swapaxes(blocks_u8, 0, 1)))
    return state


def _digest_bytes(state_words: jax.Array) -> np.ndarray:
    """[N, 8] uint32 digest words -> [N, 32] uint8 big-endian bytes."""
    w = np.asarray(state_words)
    return w.astype(">u4", order="C").view(np.uint8).reshape(-1, DIGEST_SIZE)


def _pad_block_for(length: int) -> np.ndarray:
    """The final 64-byte SHA padding block for a message of ``length`` bytes,
    valid when length % 64 == 0 (so padding occupies exactly one block)."""
    assert length % 64 == 0
    blk = np.zeros(64, dtype=np.uint8)
    blk[0] = 0x80
    blk[56:] = np.frombuffer((length * 8).to_bytes(8, "big"), dtype=np.uint8)
    return _pack_be_u32_np(blk)


def _pack_be_u32_np(b: np.ndarray) -> np.ndarray:
    return b.reshape(-1, 4).astype(np.uint32) @ np.array(
        [1 << 24, 1 << 16, 1 << 8, 1], dtype=np.uint32
    )


# Equal-length entries of a hash_batch at least this long go to the tile
# kernel (JaxPieceHasher._hash_uniform_groups), which compiles once per
# length. Torrent pieces are 4 MiB and up; everything shorter or of an odd
# length (CDC chunks <= 256 KiB, tails, blobs under a piece) goes to the
# ragged tile kernel, whose compiled shape holds no length.
_TILE_KERNEL_MIN_BYTES = 1 << 20

# Rows left for the ragged tile kernel go out as one tile of 1024 lanes
# when their blocks fill at least this many lanes of it over its whole
# block axis (the longest row's); otherwise the longest goes alone, a
# chain of its own, and the rest are asked again. The kernel's time does
# not depend on how many lanes are filled, the copy's does: a tile call
# ships 1024 rows' bytes whatever it holds, 9.1-9.6 us a block on the v5e
# against 1.0-1.1 us a block for one row's own chain (PERF.md SS5, PR 26
# sweep), so a tile pays from eight to nine lanes' worth of real blocks.
_TILE_MIN_LANES = 8


def _round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _tile_rows(rows: int) -> int:
    """Lanes the tile kernel's grid covers for ``rows`` dispatched rows:
    whole tiles of 1024 (``sha256_pallas.N_TILE``), filled or not."""
    return _round_up(rows, 1024)


def _sha_pad_into(row: np.ndarray, piece: memoryview) -> None:
    """SHA-pad one piece into the flat uint8 ``row``: its bytes, 0x80,
    zeros, the bit length. Bytes of ``row`` past the piece's own blocks
    are left as they were."""
    ln = len(piece)
    end = sha_blocks(ln) * 64
    assert end <= len(row)
    row[:ln] = np.frombuffer(piece, dtype=np.uint8)
    row[ln] = 0x80
    row[ln + 1 : end - 8] = 0
    row[end - 8 : end] = np.frombuffer(
        (ln * 8).to_bytes(8, "big"), dtype=np.uint8
    )


def _sha_pad_np(piece: memoryview, nblocks_out: int) -> np.ndarray:
    """SHA-pad one piece into [nblocks_out, 64] uint8 (zero-filled beyond)."""
    out = np.zeros((nblocks_out, 64), dtype=np.uint8)
    _sha_pad_into(out.reshape(-1), piece)
    return out


class JaxPieceHasher(PieceHasher):
    """Batched SHA-256 on the default JAX backend (TPU in production;
    registered as ``tpu`` in the hasher registry).

    ``sub_batch_bytes`` bounds the device working set per dispatch; big blobs
    stream through in sub-batches with async dispatch overlapping transfer
    and compute.
    """

    name = "tpu"

    def __init__(
        self, sub_batch_bytes: int = 256 * 1024 * 1024, use_pallas: bool | None = None
    ):
        self._sub_batch_bytes = sub_batch_bytes
        if use_pallas is None:
            # The Pallas kernel is the tuned path on real accelerators; the
            # portable XLA scan is faster than interpret-mode on CPU.
            use_pallas = jax.default_backend() != "cpu"
        self._use_pallas = use_pallas

    def devices(self) -> list:
        return jax.devices()[:1]  # every dispatch lands on the default device

    # -- blob -> per-piece digests (origin metainfo-gen hot loop) ----------

    def hash_pieces(self, data: bytes | memoryview, piece_length: int) -> np.ndarray:
        if piece_length <= 0:
            raise ValueError(f"piece_length must be positive: {piece_length}")
        view = memoryview(data)
        total = len(view)
        if total == 0:
            return np.empty((0, DIGEST_SIZE), dtype=np.uint8)
        n = (total + piece_length - 1) // piece_length
        n_full = total // piece_length

        parts: list[np.ndarray] = []
        if n_full and piece_length % 64 == 0:
            # Fast path: full pieces go up as raw uint8, zero host reshaping.
            per_batch = max(1, self._sub_batch_bytes // piece_length)
            arr = np.frombuffer(view[: n_full * piece_length], dtype=np.uint8)
            arr = arr.reshape(n_full, piece_length)
            # Bucket the batch axis (pad rows, slice results) so a short
            # final sub-batch doesn't trigger a fresh compile per blob size.
            chunks = [
                arr[s : s + per_batch] for s in range(0, n_full, per_batch)
            ]
            buckets = [min(per_batch, _next_pow2(len(c))) for c in chunks]
            if buckets[-1] != len(chunks[-1]):
                chunks[-1] = np.concatenate([
                    chunks[-1],
                    np.zeros(
                        (buckets[-1] - len(chunks[-1]), piece_length),
                        dtype=np.uint8,
                    ),
                ])
            if self._use_pallas:
                kernel, rows = "sha256_tiles", sum(map(_tile_rows, buckets))
            else:
                kernel, rows = "sha256_uniform", sum(buckets)
            # One section over every sub-batch: async dispatch overlaps the
            # copy of batch i+1 with the compute of batch i, and the result
            # is on the host only after the last.
            with device_section(
                "piece", kernel, rows=rows, blocks=sha_blocks(piece_length),
                useful_blocks=n_full * sha_blocks(piece_length),
                payload_bytes=n_full * piece_length,
                shape=(tuple(sorted(set(buckets))), piece_length),
            ):
                # Only the last sub-batch is padded, at its end.
                parts.append(_digest_bytes(
                    self._enqueue_uniform(chunks, piece_length)
                )[:n_full])
            tail = [view[i * piece_length : total] for i in range(n_full, n)]
        else:
            # Odd piece length: everything through the ragged path.
            tail = [
                view[i * piece_length : min((i + 1) * piece_length, total)]
                for i in range(n)
            ]

        if tail:
            parts.append(self._hash_batch_raw(tail, "piece"))
        _record_hash_metrics("tpu", total, n)
        return np.concatenate(parts) if len(parts) > 1 else parts[0]

    def _enqueue_uniform(
        self, chunks: list[np.ndarray], piece_length: int
    ) -> jax.Array:
        """Enqueue every [rows, piece_length] sub-batch; [sum rows, 8]
        digest words, still on the device."""
        if self._use_pallas:
            from kraken_tpu.ops.sha256_pallas import hash_pieces_device

            outs = [
                hash_pieces_device(jnp.asarray(c), piece_length) for c in chunks
            ]
        else:
            pad = jnp.asarray(_pad_block_for(piece_length))
            outs = [
                _sha256_uniform(jnp.asarray(c), pad, piece_length // 64)
                for c in chunks
            ]
        return jnp.concatenate(outs) if len(outs) > 1 else outs[0]

    # -- arbitrary piece batch (agent verify hot loop) ---------------------

    def hash_batch(
        self, pieces: list[bytes | memoryview], purpose: str = "verify"
    ) -> np.ndarray:
        if not pieces:
            return np.empty((0, DIGEST_SIZE), dtype=np.uint8)
        out = self._hash_batch_raw(pieces, purpose)
        # The agent VERIFY loop is the other north-star hot path: a TPU
        # agent that never moves hasher_bytes_total{hasher="tpu"} is
        # indistinguishable from one silently verifying on the CPU
        # (exactly the gap the live-wire e2e test pins). Recording lives
        # HERE, not in _hash_batch_raw: hash_pieces routes its ragged
        # tail through the raw variant and records the blob's FULL total
        # itself -- metrics here too would double-count the tail.
        _record_hash_metrics(
            "tpu", sum(len(memoryview(p)) for p in pieces), len(pieces)
        )
        return out

    def _hash_uniform_groups(
        self, views: list[memoryview], out: np.ndarray, purpose: str
    ) -> list[int]:
        """Hash every group of equal-length, piece-sized entries through
        the tile kernel; returns the indices left for the ragged scan.

        The scan pays a loop iteration per 64-byte block whatever the
        batch holds: on a v5e one 4 MiB piece took 27.6 s through it and
        sixteen took 4.1 s, so an agent verifying on the chip could not
        finish a 64 MiB pull inside its own 300 s download timeout. The
        tile kernel does the same batch in well under a second.
        """
        from kraken_tpu.ops.sha256_pallas import hash_pieces_device

        by_len: dict[int, list[int]] = {}
        rest: list[int] = []
        for i, v in enumerate(views):
            if len(v) >= _TILE_KERNEL_MIN_BYTES and len(v) % 64 == 0:
                by_len.setdefault(len(v), []).append(i)
            else:
                rest.append(i)
        for ln, idxs in by_len.items():
            per_batch = max(1, self._sub_batch_bytes // ln)
            for s in range(0, len(idxs), per_batch):
                group = idxs[s : s + per_batch]
                # Rows bucket to powers of FOUR: the kernel's time does not
                # depend on how many of the tile's lanes are filled, so a
                # padded row costs only its copy, while every distinct row
                # count costs a trace-and-compile of some ten seconds.
                gb = 1
                while gb < len(group):
                    gb *= 4
                rows = np.zeros((min(gb, per_batch), ln), dtype=np.uint8)
                for r, i in enumerate(group):
                    rows[r] = np.frombuffer(views[i], dtype=np.uint8)
                with device_section(
                    purpose, "sha256_tiles", rows=_tile_rows(len(rows)),
                    blocks=sha_blocks(ln),
                    useful_blocks=len(group) * sha_blocks(ln),
                    payload_bytes=len(group) * ln,
                ):
                    out[group] = _digest_bytes(
                        hash_pieces_device(jnp.asarray(rows), ln)
                    )[: len(group)]
        return rest

    def _hash_ragged_tiles(
        self, views: list[memoryview], todo: list[int], out: np.ndarray,
        purpose: str,
    ) -> None:
        """Hash ``views[i]`` for i in ``todo`` -- any lengths, any count --
        through the ragged tile kernel, digests into ``out[i]``.

        The XLA scan these rows used to take launches ~170 device ops for
        every 64-byte block (199 us a block at one row on the v5e, PERF.md
        SS5); this kernel runs the chain inside one call per slab of the
        block axis, and its two compiled shapes serve every length."""
        from kraken_tpu.ops.sha256_pallas import (
            RAGGED_ROW_SHAPE,
            RAGGED_TILE_SHAPE,
            sha256_ragged_tiles,
        )

        # Longest first: a tile's block axis is its longest row's.
        order = sorted(todo, key=lambda i: -len(views[i]))
        nblocks = np.array(
            [sha_blocks(len(views[i])) for i in order], dtype=np.int32
        )
        upto = np.concatenate(([0], np.cumsum(nblocks, dtype=np.int64)))
        n = len(order)
        s = 0
        while s < n:
            lanes, slab = RAGGED_TILE_SHAPE
            axis = _round_up(int(nblocks[s]), slab)
            # As many rows as the tile has lanes and the sub-batch budget
            # has room for, staged on the host at the longest one's length.
            g = min(lanes, n - s, self._sub_batch_bytes // (axis * 64))
            if upto[s + g] - upto[s] < _TILE_MIN_LANES * axis:
                (lanes, slab), g = RAGGED_ROW_SHAPE, 1
                axis = _round_up(int(nblocks[s]), slab)
            group = order[s : s + g]
            # Bytes past a row's own blocks are never folded: no memset.
            rows = np.empty((g, axis * 64), dtype=np.uint8)
            for row, idx in zip(rows, group):
                _sha_pad_into(row, views[idx])
            with device_section(
                purpose, "sha256_ragged_tiles", rows=lanes, blocks=axis,
                useful_blocks=int(upto[s + g] - upto[s]),
                payload_bytes=sum(len(views[idx]) for idx in group),
                shape=(lanes, slab),
            ):
                out[group] = _digest_bytes(sha256_ragged_tiles(
                    rows, nblocks[s : s + g], (lanes, slab)
                ))
            s += g

    def _hash_batch_raw(
        self, pieces: list[bytes | memoryview], purpose: str
    ) -> np.ndarray:
        if not pieces:
            return np.empty((0, DIGEST_SIZE), dtype=np.uint8)
        views = [memoryview(p) for p in pieces]
        out = np.empty((len(views), DIGEST_SIZE), dtype=np.uint8)
        if self._use_pallas:
            self._hash_ragged_tiles(
                views, self._hash_uniform_groups(views, out, purpose),
                out, purpose,
            )
            return out
        todo = list(range(len(views)))
        # Sort by size so one large piece doesn't force the whole batch to
        # its block count -- each sub-batch group buckets to its own max.
        order = sorted(todo, key=lambda i: len(views[i]))
        n = len(order)

        s = 0
        while s < n:
            # Grow the group greedily while the padded allocation
            # (pow2(count) rows x largest-piece block bucket) stays within
            # the sub-batch budget; always take at least one piece.
            g = 1
            b_bucket = _next_pow2(sha_blocks(len(views[order[s]])))
            while s + g < n:
                nxt = _next_pow2(sha_blocks(len(views[order[s + g]])))
                grown = max(b_bucket, nxt)
                if _next_pow2(g + 1) * grown * 64 > self._sub_batch_bytes:
                    break
                b_bucket = grown
                g += 1
            group = order[s : s + g]
            gb = _next_pow2(g)
            blocks = np.zeros((gb, b_bucket, 64), dtype=np.uint8)
            nblocks = np.zeros(gb, dtype=np.int32)
            for i, idx in enumerate(group):
                v = views[idx]
                blocks[i] = _sha_pad_np(v, b_bucket)
                nblocks[i] = sha_blocks(len(v))
            with device_section(
                purpose, "sha256_ragged", rows=gb, blocks=b_bucket,
                useful_blocks=int(nblocks.sum()),
                payload_bytes=sum(len(views[idx]) for idx in group),
            ):
                digests = _digest_bytes(
                    _sha256_ragged(jnp.asarray(blocks), jnp.asarray(nblocks))
                )
            for i, idx in enumerate(group):
                out[idx] = digests[i]
            s += g
        return out


register_hasher("tpu", JaxPieceHasher)
