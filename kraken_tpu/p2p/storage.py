"""Torrent storage: piece-addressed views over the CAStore.

Mirrors uber/kraken ``lib/torrent/storage`` (``Torrent`` interface with
``WritePiece``/``GetPieceReader``/``MissingPieces``...; agent archive that
allocates the cache file and persists the piece bitfield for crash-resume;
origin archive seeding completed CAStore blobs) -- upstream paths,
unverified; SURVEY.md SS2.2.

**Piece verification on write lives here** -- the agent-side hot loop the
north star routes through ``PieceHasher``: received pieces are verified by
the :class:`BatchedVerifier`, which coalesces concurrent arrivals into one
batched TPU dispatch (per BASELINE.json's agent-verify config).
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading
import time as _time
from typing import Optional

from kraken_tpu.core.digest import Digest
from kraken_tpu.core.hasher import PieceHasher, get_hasher
from kraken_tpu.core.metainfo import MetaInfo
from kraken_tpu.store import CAStore, PieceStatusMetadata
from kraken_tpu.utils.pushsteps import stepped


_log = logging.getLogger("kraken.storage")


class PieceError(Exception):
    pass


class PieceTimes:
    """``time.monotonic`` readings of one received piece on its way
    through :meth:`Torrent.write_piece`: ``handed`` (the dispatcher had
    the whole payload), ``flushed`` (the verifier's flush that took it),
    ``verdict`` (its digest compared, back on the loop) and ``written``
    (the data write returned; None for a duplicate, which writes
    nothing). The dispatcher turns them into the piece's stages."""

    __slots__ = ("handed", "flushed", "verdict", "written")

    def __init__(self, handed: float):
        self.handed = handed
        self.flushed = self.verdict = handed
        self.written: Optional[float] = None


# A device verifier's sections in flight: one holding the chip, one
# staging the next batch on the host meanwhile (BatchedVerifier).
DEVICE_SECTIONS_IN_FLIGHT = 2


class BatchedVerifier:
    """Verifies received pieces against their expected digests, batching
    concurrent arrivals into one ``PieceHasher.hash_batch`` dispatch.

    Each ``verify`` parks on a future while a flush hashes its batch off
    the event loop. How flushes are taken follows the hasher's kind:

    * host (``cpu``): a flush one event-loop tick after the first arrival,
      any number hashing at once -- hashlib runs across cores, and
      serialising host verify cost goodput 2.4x (test_data_plane_band.py).
    * device (any other hasher): at most two sections in flight
      (:data:`DEVICE_SECTIONS_IN_FLIGHT`). One holds the chip; the other is
      taken from the queue at once and stages on the host in its own
      thread -- the hop, ``hash_batch``'s row copies and the copy in --
      while the chip runs the first. A piece that finds a slot free goes on
      the next tick with whatever arrived in that tick; pieces that arrive
      while two are in flight are held, and when either ends everything
      held goes as the next section. A batch is what arrived in about one
      device cycle, so its size follows the arrival rate and the section
      time. A cap of two cannot rebuild an unbounded queue of one-piece
      sections at the chip: the chip runs sections one after another
      whatever the host does, at most one waits behind the one it runs,
      and the tile kernel takes as long over one piece as over sixteen.

    ``max_batch`` caps a flush; what is left over goes next.

    On a device hasher the chip's gaps between sections are split by what
    the host was doing (``verify_starved_seconds_total``): the last section
    in flight that ends with nothing queued stamps the time, and the next
    arrival with nothing in flight books the seconds since -- the part of
    the gap in which no piece had reached the verifier. The rest of the
    gap, to the next section's enter (``hasher_device_idle_seconds_total``),
    is the host's dispatch: the way back to the loop, the flush, the thread
    hop and the staging.
    """

    def __init__(self, hasher: PieceHasher | None = None, max_batch: int = 1024,
                 clock=_time.monotonic):
        # Public: the agent's scrubber reuses this hasher's pool for its
        # digest work (assembly wiring) -- renaming it must break loudly.
        self.hasher = hasher or get_hasher("cpu")
        self._max_batch = max_batch
        self._clock = clock
        self._device = getattr(self.hasher, "name", "cpu") != "cpu"
        self._queue: list[
            tuple[bytes, bytes, asyncio.Future, Optional[PieceTimes]]
        ] = []
        self._flusher: Optional[asyncio.Task] = None
        self._inflight: set[asyncio.Task] = set()  # strong refs to hash tasks
        self._idle_since: Optional[float] = None  # device: last section's end
        # Coalescing observability (cached refs: one flush per batch, but
        # the degenerate batch-of-1 case this exists to expose IS the
        # per-piece path): the size histogram says whether arrivals
        # actually coalesce, and the per-path batch counter splits host
        # SHA from TPU dispatches -- verify_pieces_total /
        # verify_batches_total is the average batch size on a dashboard.
        from kraken_tpu.utils.metrics import REGISTRY

        self._h_batch_size = REGISTRY.histogram(
            "verify_batch_size",
            "Pieces coalesced into each verify flush",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
        )
        self._c_batches = REGISTRY.counter(
            "verify_batches_total",
            "Verify flushes dispatched, by hash path (host|tpu)",
        )
        self._c_pieces = REGISTRY.counter(
            "verify_pieces_total", "Pieces through batched verification"
        )
        self._c_starved = REGISTRY.counter(
            "verify_starved_seconds_total",
            "Seconds a device verifier sat with no section in flight and no"
            " piece queued: from a section's end with an empty queue to the"
            " next arrival",
        )
        self._c_held = REGISTRY.counter(
            "verify_held_pieces_total",
            "Pieces that found two device verify sections in flight and were "
            "held for the next one",
        )
        self._c_overlapped = REGISTRY.counter(
            "verify_overlapped_sections_total",
            "Device verify sections taken while another verify section was in"
            " flight: staged on the host while the chip ran the other",
        )
        self._path_label = "tpu" if self._device else "host"

    async def verify(self, data: bytes | memoryview, expected: bytes,
                     times: Optional[PieceTimes] = None) -> bool:
        """``times``, when given, gets the reading of the flush that takes
        the piece (``flushed``)."""
        # ``data`` may be a pooled memoryview (zero-copy recv path): the
        # caller keeps its lease alive until this returns, and hashlib
        # consumes buffer-protocol objects directly.
        loop = asyncio.get_running_loop()
        fut: asyncio.Future[bool] = loop.create_future()
        self._queue.append((data, expected, fut, times))
        if self._idle_since is not None and not self._inflight:
            self._c_starved.inc(self._clock() - self._idle_since)
            self._idle_since = None
        if self._device and len(self._inflight) >= DEVICE_SECTIONS_IN_FLIGHT:
            self._c_held.inc()  # the first section to end flushes it
        elif len(self._queue) >= self._max_batch:
            self._flush_now()
        elif self._flusher is None or self._flusher.done():
            self._flusher = asyncio.create_task(self._flush_soon())
        return await fut

    async def _flush_soon(self) -> None:
        # One event-loop tick: every _on_payload task already scheduled
        # this tick enqueues before the flush, so a burst (pipeline-depth
        # frames landing in one recv buffer) still batches, while a
        # trickle pays no fixed delay -- a 2 ms window capped a host pair
        # at ~500 MB/s (round-5 pair profile).
        await asyncio.sleep(0)
        self._flush_now()

    @stepped("verify.flush")
    def _flush_now(self) -> None:
        if self._device and len(self._inflight) >= DEVICE_SECTIONS_IN_FLIGHT:
            return  # held: the first section to end flushes the queue
        batch = self._queue[: self._max_batch]
        self._queue = self._queue[self._max_batch :]
        if not batch:
            return
        now = self._clock()
        for _d, _e, _f, times in batch:
            if times is not None:
                times.flushed = now
        self._c_pieces.inc(len(batch))
        self._h_batch_size.observe(len(batch))
        self._c_batches.inc(1, path=self._path_label)
        if self._device and self._inflight:
            self._c_overlapped.inc()
        # The hash itself runs OFF the event loop: a full batch is hundreds
        # of MBs (CPU: ~100+ ms; TPU: a blocking device round-trip), and an
        # on-loop hash stalls every conn pump, announce, and accept for the
        # duration. hashlib releases the GIL for large buffers, so the
        # loop genuinely keeps running. Each flush resolves only its own
        # batch's futures, so host flushes hashing at once need no order.
        t = asyncio.create_task(self._hash_off_loop(batch))
        self._inflight.add(t)
        t.add_done_callback(self._section_done)

    def _section_done(self, t: asyncio.Task) -> None:
        # Runs however the section ended -- hashed, raised or cancelled --
        # so a failed batch never wedges the device slot and every pull
        # behind it.
        self._inflight.discard(t)
        if not self._device:
            return
        if self._queue:
            self._flush_now()
        elif not self._inflight:
            self._idle_since = self._clock()  # starved until the next arrival

    @stepped("verify.flush")
    async def _hash_off_loop(
        self, batch: list[tuple[bytes, bytes, asyncio.Future, object]]
    ) -> None:
        # Drop abandoned entries BEFORE touching their buffers: a waiter
        # cancelled mid-verify (torrent teardown, peer drop) releases its
        # pooled payload buffer from the task's done-callback, and the
        # verifier is SHARED across torrents -- hashing a released
        # memoryview would fail the whole batch and blacklist innocent
        # peers of unrelated torrents. A cancelled await marks its future
        # done, so this filter removes exactly the doomed entries.
        batch = [entry for entry in batch if not entry[2].done()]
        if not batch:
            return
        try:
            digests = await asyncio.to_thread(
                self.hasher.hash_batch, [entry[0] for entry in batch]
            )
        except Exception:
            # One bad entry (e.g. a buffer released in the race window
            # between the filter above and the hash) must not fail its
            # batch-mates: retry per item, failing only what individually
            # fails.
            for d, expected, fut, _times in batch:
                if fut.done():
                    continue
                try:
                    got = await asyncio.to_thread(
                        self.hasher.hash_batch, [d]
                    )
                    fut.set_result(bytes(got[0]) == expected)
                except Exception as e:
                    if not fut.done():
                        fut.set_exception(e)
            return
        for (_d, expected, fut, _times), got in zip(batch, digests):
            if not fut.done():
                fut.set_result(bytes(got) == expected)


class _FlatIO:
    """Raw-fd IO handle for flat-file torrents: the pread/pwrite/close
    trio :class:`Torrent` ref-counts, shaped exactly like the chunk
    tier's ChunkReader so both storage representations share the piece
    IO path (reads; only flat files ever take writes)."""

    __slots__ = ("_fd",)

    def __init__(self, fd: int):
        self._fd = fd

    def pread(self, n: int, off: int) -> bytes:
        return os.pread(self._fd, n, off)

    def pwrite(self, data, off: int) -> int:
        return os.pwrite(self._fd, data, off)

    def close(self) -> None:
        os.close(self._fd)


class Torrent:
    """Piece-addressed access to one blob in the store.

    Complete torrents (origin seeding) read straight from the committed
    blob. Incomplete torrents own a pre-allocated cache file plus the
    persisted piece bitfield; the final ``write_piece`` completes them.
    """

    def __init__(
        self,
        store: CAStore,
        metainfo: MetaInfo,
        verifier: BatchedVerifier,
        complete: bool = False,
        path: Optional[str] = None,
    ):
        self.store = store
        self.metainfo = metainfo
        self._verifier = verifier
        # Serve-while-ingest: a complete torrent whose bytes still live at
        # the upload spool path (every byte is on disk; commit is just the
        # rename). promote() repoints it at the cache path post-commit --
        # an fd opened on the spool keeps working because rename preserves
        # the inode. While spool_backed, shard handoff is skipped: the
        # worker's long-lived fd would outlive a commit failure's unlink.
        self.spool_backed = False
        if complete:
            if path is not None:
                self._path = path
                self.spool_backed = True
            else:
                self._path = store.cache_path(metainfo.digest)
            self._status = None  # complete: no bitfield needed
        else:
            # Incomplete data lives at the partial path until the last
            # piece lands; only then is it renamed into the cache, so
            # ``in_cache`` can never observe a half-written blob.
            self._path = store.partial_path(metainfo.digest)
            md = store.get_metadata(metainfo.digest, PieceStatusMetadata)
            self._status = md or PieceStatusMetadata(metainfo.num_pieces)
        # Serializes bitfield updates + completion check.
        self._lock = asyncio.Lock()
        self._full_bits: Optional[bytes] = None  # memoized complete bitfield
        # One long-lived fd + os.pread/pwrite replace the per-piece
        # open/seek/read/close of earlier rounds: positional IO is
        # thread-safe (no shared file offset), so piece reads and writes
        # from worker threads need no lock and no file-table churn. The
        # pair-profile (PERF.md round 5) localized ~35% of the wall to
        # exactly this machinery.
        self._fd: Optional[int] = None
        self._fd_lock = threading.Lock()
        self._fd_refs = 0  # in-flight pread/pwrite count (teardown gate)
        self._fd_closed = False
        # Bitfield persistence is DEBOUNCED (the round-5 pair profile's
        # single largest cost was one sidecar rename per piece, on the
        # event loop): pieces mark the bitfield dirty, a per-torrent
        # flusher persists it at most every BITS_FLUSH_SECONDS, and
        # close()/completion flush what remains. Crash window: pieces
        # landed since the last flush are re-downloaded on resume -- the
        # persisted bitfield may UNDERstate progress, never overstate it
        # (bits are set only after their piece's data write returns).
        self._bits_dirty = False
        self._bits_flusher: Optional[asyncio.Task] = None
        # Cumulative per-piece stage walls for the torrent_summary
        # stage split (dispatch.py): how long this torrent's pieces
        # spent from the dispatcher's hand-off to the verdict (queue +
        # verify) vs the data write. Pieces pipeline, so
        # these OVERLAP each other and the wire wait -- they sum past
        # the pull's wall clock; they are stage COSTS, not a timeline.
        self.verify_wall = 0.0
        self.write_wall = 0.0

    BITS_FLUSH_SECONDS = 0.2

    # -- introspection -----------------------------------------------------

    @property
    def digest(self) -> Digest:
        return self.metainfo.digest

    @property
    def info_hash(self):
        return self.metainfo.info_hash

    @property
    def num_pieces(self) -> int:
        return self.metainfo.num_pieces

    @property
    def blob_path(self) -> str:
        """Filesystem path of the backing file (the committed cache path
        once complete) -- what the seed-serve worker shards open for
        their long-lived sendfile fd. A chunk-backed blob has NO flat
        path: the scheduler's shard handoff checks existence and keeps
        such conns on the main loop, whose piece reads compose through
        the chunk tier (materialize_flat is the opt-in escape hatch)."""
        return self._path

    def complete(self) -> bool:
        return self._status is None or self._status.complete()

    def has_piece(self, i: int) -> bool:
        return self._status is None or self._status.has(i)

    def missing_pieces(self) -> list[int]:
        return [] if self._status is None else self._status.missing()

    def num_pieces_complete(self) -> int:
        return self.num_pieces if self._status is None else self._status.count()

    def bitfield(self) -> bytes:
        if self._status is None:
            # Memoized: a seeder rebuilds this for EVERY inbound handshake,
            # and O(pieces) per handshake x a full conn budget on a
            # 10k-piece blob is real loop time.
            if self._full_bits is None:
                full = PieceStatusMetadata(self.num_pieces)
                for i in range(self.num_pieces):
                    full.set(i)
                self._full_bits = bytes(full.bits)
            return self._full_bits
        return bytes(self._status.bits)

    # -- pieces ------------------------------------------------------------

    def _open_io(self):
        """The torrent's IO handle: a raw fd on the backing file, or --
        for a COMPLETE blob whose bytes live in the chunk tier -- a
        composed :class:`~kraken_tpu.store.chunkstore.ChunkReader`.
        Both expose ``pread``; only the flat handle can ``pwrite``
        (incomplete torrents always write into a flat ``.part``)."""
        if self._status is None:
            try:
                fd = os.open(self._path, os.O_RDONLY)
            except FileNotFoundError:
                reader = self.store._chunk_reader(self.metainfo.digest)
                if reader is None:
                    raise
                return reader
            return _FlatIO(fd)
        # O_RDWR while incomplete (piece writes land here); a committed
        # blob is read-only. Completion does NOT reopen: commit is a
        # rename, so the fd keeps addressing the same inode the cache
        # path now names.
        return _FlatIO(os.open(self._path, os.O_RDWR))

    def _with_fd(self, op):
        """Run ``op(io)`` (a pread/pwrite) with the handle ref-counted.

        Teardown races are real: cancelling an _io_task does NOT stop a
        worker thread already inside os.pwrite, and closing the fd under
        it risks EBADF -- or, via fd-number reuse, a multi-MiB write into
        whatever file grabbed the number. So close() only marks closed;
        the LAST in-flight op (or close() itself when none are) actually
        closes, and new ops after close are refused."""
        with self._fd_lock:
            if self._fd_closed:
                raise PieceError("torrent closed")
            if self._fd is None:
                self._fd = self._open_io()
            self._fd_refs += 1
            fd = self._fd
        try:
            return op(fd)
        finally:
            with self._fd_lock:
                self._fd_refs -= 1
                if self._fd_closed and self._fd_refs == 0 and self._fd is not None:
                    self._fd.close()
                    self._fd = None

    def release_fd(self) -> None:
        """Drop the cached IO handle if no IO is in flight; the next
        piece IO reopens it. The dispatcher calls this when a torrent's
        last peer leaves, so a long-lived origin seeding thousands of
        blobs holds fds only for torrents with LIVE conns -- without
        this, steady-state fd usage grows with every blob ever served
        until EMFILE (and conn churn already guarantees idle torrents
        shed their peers). Best-effort: in-flight IO keeps the handle
        until close()."""
        with self._fd_lock:
            if self._fd_refs == 0 and self._fd is not None and not self._fd_closed:
                self._fd.close()
                self._fd = None

    def close(self) -> None:
        """Flush any unpersisted bitfield and retire the fd. Sync --
        callable from dispatcher teardown. Only incomplete torrents flush
        (a complete torrent has no sidecar; re-writing one after eviction
        would orphan a ._md file beside a deleted blob).

        The flush runs OFF the event loop when one is running, matching
        the periodic flusher and the commit path: in durability=fsync
        mode a sidecar write pays fsync+dirsync, and a watermark sweep
        tearing down many torrents would otherwise stall every conn pump
        for the duration (VERDICT r5 weak #3). Without a loop (tests,
        sync teardown) it blocks right here. Best-effort either way: the
        persisted bitfield may understate progress, never overstate it."""
        if self._bits_flusher is not None:
            self._bits_flusher.cancel()
            self._bits_flusher = None
        if self._status is not None and self._bits_dirty:
            status = self._status
            self._bits_dirty = False

            def _flush() -> None:
                try:
                    self.store.set_metadata(self.metainfo.digest, status)
                except Exception:
                    # Progress-only sidecar: a lost flush re-downloads at
                    # most the unflushed tail on resume.
                    _log.warning(
                        "final bitfield flush failed",
                        extra={"digest": self.metainfo.digest.hex},
                        exc_info=True,
                    )

            try:
                loop = asyncio.get_running_loop()
                loop.run_in_executor(None, _flush)
            except RuntimeError:
                # No loop, or the loop's executor already shut down
                # (process teardown): flush inline -- blocking here
                # beats losing the progress entirely.
                _flush()
        with self._fd_lock:
            self._fd_closed = True
            if self._fd_refs == 0 and self._fd is not None:
                self._fd.close()
                self._fd = None

    def promote(self, path: str) -> None:
        """Repoint a spool-backed torrent at its committed path (commit
        renamed the spool into the cache, same inode). New opens hit the
        cache path; an fd already open on the old name is unaffected."""
        with self._fd_lock:
            self._path = path
            self.spool_backed = False

    def read_piece(self, i: int) -> bytes:
        if not self.has_piece(i):
            raise PieceError(f"piece {i} not present")
        off = i * self.metainfo.piece_length
        ln = self.metainfo.piece_length_of(i)
        data = self._with_fd(lambda io_: io_.pread(ln, off))
        if len(data) != ln:
            raise PieceError(f"short read on piece {i}")
        return data

    async def write_piece(
        self,
        i: int,
        data: bytes | memoryview,
        remote_write=None,
        times: Optional[PieceTimes] = None,
    ) -> bool:
        """Verify + persist piece ``i``. Returns True when this write
        completed the torrent. Raises :class:`PieceError` on corrupt data
        (callers blacklist the sender). File IO runs off-loop so a disk
        stall can't freeze the scheduler. ``data`` may be a pooled
        memoryview flowing straight from the wire to ``os.pwrite`` --
        the caller releases its lease only after this returns.

        ``remote_write`` (leech-shard plane): an async callable taking
        the piece index that persists the already-verified bytes in the
        WORKER that received them -- the payload stays in its shared-
        memory slot and never crosses back to this process. It replaces
        only the data-write step; verify, duplicate checks, the bit
        mark, and commit all stay here, so the crash-resume invariant
        (bit set only after the data is durably written) holds
        unchanged. A remote write that fails (worker died mid-flight)
        raises, the piece stays unmarked, and the dispatcher requeues
        it like any peer error.

        ``times`` (the dispatcher's, from when it had the payload) gets
        the piece's readings; without it they start here. The stage
        walls add ``verdict - handed`` and ``written - verdict``."""
        if self._status is None:
            # With endgame duplication a second copy of the final piece
            # can arrive after completion: a benign duplicate, never a
            # peer fault.
            return False
        if len(data) != self.metainfo.piece_length_of(i):
            raise PieceError(
                f"piece {i}: wrong length {len(data)} != "
                f"{self.metainfo.piece_length_of(i)}"
            )
        if times is None:
            times = PieceTimes(_time.monotonic())
        if not await self._verifier.verify(
            data, self.metainfo.piece_hash(i), times
        ):
            raise PieceError(f"piece {i}: digest mismatch")
        times.verdict = _time.monotonic()
        self.verify_wall += times.verdict - times.handed
        if self._status is None or self._status.has(i):
            return False  # duplicate arrival (endgame copies are benign)
        # The data write runs OUTSIDE the lock: pieces occupy disjoint
        # offsets, so concurrent pwrites never conflict, and serializing
        # 4 MiB disk writes behind one asyncio.Lock was the round-4
        # pair-throughput cap. A duplicate slipping past the pre-check
        # rewrites identical bytes -- benign. Completion cannot race this
        # write: it requires every bit set, and piece i's bit is only set
        # below, after this write returns.
        if remote_write is not None:
            await remote_write(i)
        else:
            await asyncio.to_thread(self._write_at, i, data)
        times.written = _time.monotonic()
        self.write_wall += times.written - times.verdict
        async with self._lock:
            # Re-check under the lock: a concurrent writer of the same
            # final piece may have completed the torrent (set _status to
            # None) while this task parked on verify or the write.
            if self._status is None or self._status.has(i):
                return False
            self._status.set(i)
            if self._status.complete():
                if self._bits_flusher is not None:
                    self._bits_flusher.cancel()
                    self._bits_flusher = None
                self._bits_dirty = False

                def _commit() -> None:
                    # Off-loop: in durability=fsync mode this fsyncs the
                    # WHOLE blob -- seconds for multi-GiB, which on the
                    # loop would stall every conn pump on the agent.
                    self.store.commit_partial_file(self.metainfo.digest)
                    self.store.delete_metadata(
                        self.metainfo.digest, PieceStatusMetadata
                    )

                await asyncio.to_thread(_commit)
                self._status = None
                self._path = self.store.cache_path(self.metainfo.digest)
                return True
            self._mark_bits_dirty()
            return False

    def _write_at(self, i: int, data: bytes) -> None:
        self._with_fd(
            lambda io_: io_.pwrite(data, i * self.metainfo.piece_length)
        )

    def _mark_bits_dirty(self) -> None:
        self._bits_dirty = True
        if self._bits_flusher is None or self._bits_flusher.done():
            self._bits_flusher = asyncio.create_task(self._flush_bits_later())

    async def _flush_bits_later(self) -> None:
        await asyncio.sleep(self.BITS_FLUSH_SECONDS)
        async with self._lock:
            if self._status is not None and self._bits_dirty:
                # Off-loop: a sidecar write is small, but in fsync mode
                # it pays fsync+dirsync every flush.
                await asyncio.to_thread(
                    self.store.set_metadata, self.metainfo.digest, self._status
                )
                self._bits_dirty = False

    async def read_piece_async(self, i: int) -> bytes:
        """Off-loop :meth:`read_piece` for pump-context reads."""
        return await asyncio.to_thread(self.read_piece, i)

    async def flush_bits(self) -> None:
        """Persist the piece bitfield NOW (off-loop), ahead of the
        debounced flusher. The delta prefill hands its progress to a
        fresh Torrent immediately after closing this one -- waiting out
        the 200 ms debounce window (or racing close()'s fire-and-forget
        executor flush) would let the successor re-download pieces this
        torrent already verified and wrote."""
        async with self._lock:
            if self._status is not None and self._bits_dirty:
                await asyncio.to_thread(
                    self.store.set_metadata, self.metainfo.digest, self._status
                )
                self._bits_dirty = False


class AgentTorrentArchive:
    """Download-side archive: creates resumable torrents from metainfo.

    Mirrors ``lib/torrent/storage/agentstorage`` (metainfo via tracker,
    cache-file allocation, bitfield persistence) -- the metainfo fetch
    lives in the caller (scheduler) to keep this layer IO-free.
    """

    def __init__(self, store: CAStore, verifier: BatchedVerifier):
        self.store = store
        self.verifier = verifier

    def create_torrent(self, metainfo: MetaInfo) -> Torrent:
        # On-loop IO audit (VERDICT r5 #6): this runs on the loop (the
        # scheduler's sync control setup) and writes the initial bitfield
        # sidecar -- once per NEW torrent, not per piece, so the fsync-
        # mode cost is one sync per download start. Acceptable; the
        # per-piece paths (verify, data write, bitfield flush, commit,
        # close) all run off-loop.
        d = metainfo.digest
        if self.store.in_cache(d):
            # in_cache == committed (partials live at .part), so this is
            # always safe to seed.
            return Torrent(self.store, metainfo, self.verifier, complete=True)
        self.store.allocate_partial_file(d, metainfo.length)
        if self.store.get_metadata(d, PieceStatusMetadata) is None:
            self.store.set_metadata(d, PieceStatusMetadata(metainfo.num_pieces))
        return Torrent(self.store, metainfo, self.verifier, complete=False)


class OriginTorrentArchive:
    """Seed-side archive: torrents over committed CAStore blobs."""

    def __init__(self, store: CAStore, verifier: BatchedVerifier):
        self.store = store
        self.verifier = verifier

    def create_torrent(self, metainfo: MetaInfo) -> Torrent:
        if not self.store.in_cache(metainfo.digest):
            raise KeyError(str(metainfo.digest))
        return Torrent(self.store, metainfo, self.verifier, complete=True)
