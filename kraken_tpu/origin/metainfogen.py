"""Metainfo generation: the origin-side piece-hash hot loop, on TPU.

Mirrors uber/kraken ``lib/metainfogen`` (``Generator.Generate(digest)``:
choose piece length from blob size via a config table, checksum every
piece, write MetaInfo to the store) -- upstream path, unverified; SURVEY.md
SS2.3. **Primary TPU offload target** (BASELINE.json): the per-piece hashing
goes through the batched ``PieceHasher`` -- one TPU dispatch per blob
instead of a sequential CPU loop.

The generated MetaInfo persists as a metadata sidecar of the blob, so
restarts never re-hash.
"""

from __future__ import annotations

import asyncio
import dataclasses

import numpy as np

from kraken_tpu.core.digest import Digest
from kraken_tpu.core.hasher import PieceHasher, get_hasher
from kraken_tpu.core.metainfo import MetaInfo
from kraken_tpu.store import CAStore, Metadata, register_metadata
from kraken_tpu.utils.pushsteps import stepped


@register_metadata
class TorrentMetaMetadata(Metadata):
    """The blob's serialized MetaInfo, stored beside it."""

    name = "torrentmeta"

    def __init__(self, metainfo: MetaInfo):
        self.metainfo = metainfo

    def serialize(self) -> bytes:
        return self.metainfo.serialize()

    @classmethod
    def deserialize(cls, raw: bytes) -> "TorrentMetaMetadata":
        return cls(MetaInfo.deserialize(raw))


@dataclasses.dataclass(frozen=True)
class PieceLengthConfig:
    """Blob size -> piece length table (powers of two), as the reference
    configures. Defaults: small blobs get 4 MiB pieces; larger blobs scale
    up so the piece count stays bounded."""

    # (min blob size, piece length), evaluated top-down; last match wins.
    table: tuple[tuple[int, int], ...] = (
        (0, 4 * 1024 * 1024),
        (2 * 1024**3, 8 * 1024 * 1024),
        (8 * 1024**3, 16 * 1024 * 1024),
    )

    def piece_length(self, blob_size: int) -> int:
        chosen = self.table[0][1]
        for min_size, piece_len in self.table:
            if blob_size >= min_size:
                chosen = piece_len
        return chosen


class Generator:
    """Generates (and caches) MetaInfo for blobs in a CAStore."""

    def __init__(
        self,
        store: CAStore,
        hasher: PieceHasher | None = None,
        piece_lengths: PieceLengthConfig | None = None,
        window_bytes: int = 256 * 1024 * 1024,
        pipeline=None,
    ):
        self.store = store
        self.hasher = hasher or get_hasher("cpu")
        self.piece_lengths = piece_lengths or PieceLengthConfig()
        # Blobs are hashed through a sliding window of whole pieces, so
        # generation memory is O(window), not O(blob). The window is the
        # hasher's batch.
        self.window_bytes = window_bytes
        # core.ingest.IngestPipeline, when the origin runs the pipelined
        # ingest plane: re-generates stream spool windows through it
        # (read overlapping transfer/hash) instead of the serial
        # read-then-hash loop below. None = serial path.
        self.pipeline = pipeline

    @stepped("metainfo.read")
    def get_cached(self, d: Digest) -> MetaInfo | None:
        md = self.store.get_metadata(d, TorrentMetaMetadata)
        return md.metainfo if md else None

    def generate_sync(self, d: Digest) -> MetaInfo:
        """Hash every piece of blob ``d`` (windowed batched dispatches) and
        persist the MetaInfo. Idempotent. Raises KeyError if the blob is
        absent."""
        cached = self.get_cached(d)
        if cached is not None:
            return cached
        size = self.store.cache_size(d)  # KeyError if absent
        piece_length = self.piece_lengths.piece_length(size)
        if self.pipeline is not None:
            hashes = self._generate_pipelined(d, piece_length)
            metainfo = MetaInfo(d, size, piece_length, hashes.tobytes())
            self.store.set_metadata(d, TorrentMetaMetadata(metainfo))
            return metainfo
        # Floor the window at a FEW pieces when a hash pool exists, so a
        # tiny configured window cannot fully serialize the sharded
        # piece pass -- but cap the floor at 4 pieces: window_bytes is
        # the operator's MEMORY bound, and flooring at workers pieces
        # would silently inflate it ~(workers/windowpieces)x on many-core
        # origins (16 MiB pieces x 62 workers = ~1 GiB/window). A window
        # of k pieces still shards k ways; full occupancy wants
        # window_bytes >= workers * piece_length, which OPERATIONS.md
        # leaves to the operator.
        pool = getattr(self.hasher, "pool", None)  # duck-typed test hashers
        min_pieces = min(pool.workers, 4) if pool is not None else 1
        window = max(
            piece_length * min_pieces,
            self.window_bytes // piece_length * piece_length,
        )
        parts = []
        # One-window lookahead: the read of window i+1 runs in a side
        # thread while the hasher chews window i, so a TPU dispatch never
        # waits on disk (and a cold page cache never waits on the device).
        # generate() already runs off-loop, so blocking on the prefetch
        # here is fine.
        from concurrent.futures import ThreadPoolExecutor

        with self.store.open_cache_file(d) as f, ThreadPoolExecutor(1) as ex:
            data = f.read(window)
            while True:
                prefetch = ex.submit(f.read, window)
                parts.append(self.hasher.hash_pieces(data, piece_length))
                if len(data) < window:
                    break
                data = prefetch.result()
                if not data:
                    break
        hashes = parts[0] if len(parts) == 1 else np.concatenate(parts)
        metainfo = MetaInfo(d, size, piece_length, hashes.tobytes())
        self.store.set_metadata(d, TorrentMetaMetadata(metainfo))
        return metainfo

    def _generate_pipelined(self, d: Digest, piece_length: int) -> np.ndarray:
        """Stream the blob through the ingest pipeline: ``readinto`` lands
        each window's bytes DIRECTLY in the staging buffer the hasher
        consumes (the zero-copy read stage), and the pipeline overlaps
        window k+1's read with window k's transfer/hash. Digests are
        bit-identical to the serial loop -- same piece boundaries."""
        ses = self.pipeline.session(piece_length)
        try:
            with self.store.open_cache_file(d) as f:
                while True:
                    buf = ses.begin_window()
                    n = f.readinto(buf)
                    ses.submit(n or 0)
                    if not n or n < len(buf):
                        break
            return ses.finish()
        except BaseException:
            ses.abort()
            raise

    async def generate(self, d: Digest) -> MetaInfo:
        """Off-loop :meth:`generate_sync` (reads + hashes a whole blob)."""
        return await asyncio.to_thread(self.generate_sync, d)

    @stepped("commit.adopt")
    def adopt(
        self, d: Digest, size: int, piece_length: int, piece_hashes: bytes
    ) -> MetaInfo:
        """Persist a MetaInfo whose piece hashes the CALLER computed while
        the bytes streamed in (origin stream-time piece hashing) -- the
        blob is never re-read. The piece length must match this
        generator's config for ``size`` so agents and the re-generate
        path agree bit-for-bit."""
        if piece_length != self.piece_lengths.piece_length(size):
            raise ValueError(
                f"piece_length {piece_length} != configured "
                f"{self.piece_lengths.piece_length(size)} for size {size}"
            )
        metainfo = MetaInfo(d, size, piece_length, piece_hashes)
        self.store.set_metadata(d, TorrentMetaMetadata(metainfo))
        return metainfo
