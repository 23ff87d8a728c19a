"""Sharded piece hashing: the hash plane over a chip mesh.

Replaces the reference's scale-by-adding-origin-hosts story for the hot
loop (uber/kraken ``lib/metainfogen`` -- upstream path, unverified;
SURVEY.md SS2.3) with in-host chip scaling: ``shard_map`` splits the piece
batch across the ``pieces`` mesh axis, each chip runs the identical
single-chip kernel (Pallas on real TPUs, interpret/XLA-scan on CPU), and
the [N, 8] digest matrix is optionally all-gathered to every chip (32
bytes/piece -- the collective is noise next to the hashing itself).

Every placement on the mesh is explicit (``jax.device_put`` with a
``NamedSharding``): a caller may hand in a mesh that is not on the
default platform, and a stray default-device ``jnp.asarray`` would land
off it.
"""

from __future__ import annotations

import functools
import logging

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kraken_tpu.core.hasher import (
    DIGEST_SIZE,
    PieceHasher,
    device_section,
    record_hash_metrics,
    register_hasher,
    sha_blocks,
)
from kraken_tpu.ops.sha256 import (
    _digest_bytes,
    _pad_block_for,
    _sha256_uniform,
    JaxPieceHasher,
)
from kraken_tpu.utils.metrics import REGISTRY

_log = logging.getLogger("kraken.hashplane")


@functools.lru_cache(maxsize=32)
def _sharded_fn(
    mesh: Mesh,
    unpadded_blocks: int,
    use_pallas: bool,
    interpret: bool,
    replicate: bool,
):
    """Compile-cached sharded hash step for one (mesh, shape-bucket) pair."""

    def per_shard(data_u8, pad_block):
        if use_pallas:
            from kraken_tpu.ops.sha256_pallas import hash_pieces_device

            return hash_pieces_device(
                data_u8, unpadded_blocks * 64, interpret=interpret
            )
        return _sha256_uniform(data_u8, pad_block, unpadded_blocks)

    mapped = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P("pieces", None), P()),
        out_specs=P("pieces", None),
        # Purely data-parallel map: the varying-manual-axes analysis trips
        # on the replicated H0 carry entering the per-shard scan.
        check_vma=False,
    )
    out_spec = P() if replicate else P("pieces", None)
    return jax.jit(mapped, out_shardings=NamedSharding(mesh, out_spec))


def stage_sharded_pieces(
    mesh: Mesh, data_u8: np.ndarray, piece_length: int
) -> tuple[jax.Array, int]:
    """TRANSFER stage of the sharded hash: pad [M, piece_length] uint8 to
    the mesh's device quantum and ``jax.device_put`` it row-sharded over
    the ``pieces`` axis. Returns ``(staged, m)`` for
    :func:`hash_sharded_staged`. Split out so the ingest pipeline can
    overlap window k+1's host->device transfer with window k's hash (and
    bill each to its own stage wall)."""
    if piece_length % 64:
        raise ValueError("sharded path requires piece_length % 64 == 0")
    n_dev = mesh.devices.size
    m = data_u8.shape[0]
    # Equal shards are mandatory under shard_map; pallas additionally pads
    # each shard to its tile internally, so only the device quantum matters.
    pad_rows = (-m) % n_dev
    if pad_rows:
        data_u8 = np.concatenate(
            [data_u8, np.zeros((pad_rows, piece_length), dtype=np.uint8)]
        )
    x = jax.device_put(data_u8, NamedSharding(mesh, P("pieces", None)))
    return x, m


def hash_sharded_staged(
    mesh: Mesh,
    staged: jax.Array,
    m: int,
    piece_length: int,
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
    replicate: bool = True,
) -> jax.Array:
    """HASH stage over an already-staged (device-resident, row-sharded)
    window from :func:`stage_sharded_pieces`."""
    if interpret is None:
        interpret = mesh.devices.flat[0].platform == "cpu"
    pad_block = jax.device_put(
        _pad_block_for(piece_length), NamedSharding(mesh, P())
    )
    fn = _sharded_fn(
        mesh, piece_length // 64, use_pallas, bool(interpret), replicate
    )
    out = fn(staged, pad_block)
    if staged.shape[0] != m:
        # Static-index slice: a dynamic `out[:m]` gather eagerly transfers
        # its int32 start index to the DEFAULT device -- the round-2 driver
        # failure, where that device was a version-skewed real TPU.
        out = jax.lax.slice_in_dim(out, 0, m)
    return out


def sharded_hash_pieces(
    mesh: Mesh,
    data_u8: np.ndarray,
    piece_length: int,
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
    replicate: bool = True,
) -> jax.Array:
    """Hash [M, piece_length] uint8 pieces data-parallel over ``mesh``.

    Returns [M, 8] uint32 digest words; with ``replicate=True`` the result
    is all-gathered (replicated on every mesh device) for downstream
    consumers like the dedup similarity search. piece_length must be a
    multiple of 64 (the uniform fast path; ragged tails go through the
    single-chip ragged kernel upstream of this call).
    """
    staged, m = stage_sharded_pieces(mesh, data_u8, piece_length)
    return hash_sharded_staged(
        mesh, staged, m, piece_length,
        use_pallas=use_pallas, interpret=interpret, replicate=replicate,
    )


class ShardedPieceHasher(PieceHasher):
    """PieceHasher that fans the uniform fast path across every local chip.

    Drop-in for the single-chip ``tpu`` hasher (``hasher: tpu-sharded`` in
    origin/agent YAML). Ragged tail pieces fall back to the single-chip
    ragged path -- they are a rounding error of the work.
    """

    name = "tpu-sharded"

    def __init__(self, mesh: Mesh | None = None, use_pallas: bool | None = None):
        from kraken_tpu.parallel.mesh import piece_mesh

        self._mesh = mesh if mesh is not None else piece_mesh()
        if use_pallas is None:
            use_pallas = self._mesh.devices.flat[0].platform != "cpu"
        self._use_pallas = use_pallas
        # hash_batch (agent verify, dedup chunks) and ragged tails are not
        # sharded: they run on the default device through the single-chip
        # hasher, which needs the tile kernel for piece-sized batches as
        # much as a `tpu` agent does.
        self._fallback = JaxPieceHasher(use_pallas=use_pallas)
        self._dispatched = False
        self._mesh_rows = REGISTRY.counter(
            "hasher_mesh_rows_total",
            "Rows of sharded dispatches each mesh device took, padding"
            " rows included",
        )
        self._mesh_pad_rows = REGISTRY.counter(
            "hasher_mesh_pad_rows_total",
            "Rows of zeros sharded dispatches sent to fill the mesh's"
            " device quantum",
        )

    def devices(self) -> list:
        return list(self._mesh.devices.flat)

    def _hash_staged(self, staged: jax.Array, m: int, piece_length: int):
        # Which devices really hold rows. A mesh that collapsed onto one
        # device hashes just as correctly.
        rows_per_device = {
            str(s.device.id): int(s.data.shape[0])
            for s in staged.addressable_shards
        }
        if not self._dispatched:
            self._dispatched = True  # once per process
            _log.info(
                "sharded hasher first dispatch",
                extra={"rows_per_device": rows_per_device},
            )
        with self._section("sha256_sharded", staged.shape[0], m, piece_length):
            for device, rows in rows_per_device.items():
                self._mesh_rows.inc(rows, device=device)
            self._mesh_pad_rows.inc(staged.shape[0] - m)
            return _digest_bytes(
                hash_sharded_staged(
                    self._mesh, staged, m, piece_length,
                    use_pallas=self._use_pallas, replicate=False,
                )
            )

    def _stage(self, arr: np.ndarray, piece_length: int):
        n_dev = self._mesh.devices.size
        m = arr.shape[0]
        with self._section("device_put", m + (-m) % n_dev, m, piece_length):
            return stage_sharded_pieces(self._mesh, arr, piece_length)

    @staticmethod
    def _section(kernel: str, rows: int, m: int, piece_length: int):
        """A device section of the acknowledged path over ``m`` full
        pieces dispatched as ``rows`` (the mesh's device quantum)."""
        blocks = sha_blocks(piece_length)
        return device_section(
            "piece", kernel, rows=rows, blocks=blocks,
            useful_blocks=m * blocks, payload_bytes=m * piece_length,
        )

    def hash_pieces(self, data, piece_length: int) -> np.ndarray:
        if piece_length <= 0:
            raise ValueError(f"piece_length must be positive: {piece_length}")
        view = memoryview(data)
        total = len(view)
        if total == 0:
            return np.empty((0, DIGEST_SIZE), dtype=np.uint8)
        if piece_length % 64:
            return self._fallback.hash_pieces(data, piece_length)
        n_full = total // piece_length
        n = (total + piece_length - 1) // piece_length
        out = []
        if n_full:
            arr = np.frombuffer(view[: n_full * piece_length], dtype=np.uint8)
            staged, m = self._stage(
                arr.reshape(n_full, piece_length), piece_length
            )
            out.append(self._hash_staged(staged, m, piece_length))
        if n > n_full:  # ragged tail piece (raw: this call records the
            # blob's FULL total below -- the metric-wrapping hash_batch
            # would double-count the tail bytes under hasher="tpu")
            out.append(
                self._fallback._hash_batch_raw(
                    [view[n_full * piece_length :]], "piece"
                )
            )
        # Same counters as the single-chip hashers -- a sharded origin
        # must not go dark on dashboards.
        record_hash_metrics(self.name, total, n)
        return np.concatenate(out) if len(out) > 1 else out[0]

    def hash_batch(self, pieces, purpose: str = "verify") -> np.ndarray:
        return self._fallback.hash_batch(pieces, purpose)

    # -- staged-window protocol (core/ingest.py pipeline) ----------------
    # stage_window/hash_staged_window split hash_pieces at the host->
    # device boundary so the pipeline can overlap window k+1's transfer
    # with window k's hash and attribute each to its own stage wall.
    # Digests are bit-identical to hash_pieces by construction (the same
    # sharded fn runs on the same rows).

    def stage_window(self, arr: np.ndarray, piece_length: int):
        """Transfer one UNIFORM window ([M, piece_length] uint8, every row
        a full piece) to the mesh. Returns an opaque staged handle."""
        staged, m = self._stage(arr, piece_length)
        return (staged, m, piece_length)

    def hash_staged_window(self, handle) -> np.ndarray:
        """Hash a :meth:`stage_window` handle -> [M, 32] uint8 digests."""
        staged, m, piece_length = handle
        out = self._hash_staged(staged, m, piece_length)
        record_hash_metrics(self.name, m * piece_length, m)
        return out


register_hasher("tpu-sharded", ShardedPieceHasher)
