"""Size-classed payload buffer pool for the P2P wire plane.

The round-5 residual decomposition (PERF.md) put the next data-plane
bound on per-piece allocation/copy churn: every received PIECE_PAYLOAD
materialized a fresh payload-sized ``bytes`` (plus a second full copy for
the ``raw[header_len:]`` slice), and at 1 MiB pieces that allocator +
memcpy traffic is pure CPU-per-byte on the event-loop core. The pool
replaces both with a leased ``bytearray`` reused across pieces: the wire
reads straight into it, the ``memoryview`` flows through verify and
``os.pwrite`` untouched, and one explicit :meth:`Lease.release` returns
the buffer after the bitfield mark.

Size classes are powers of two (floor 4 KiB): a lease for ``n`` bytes
draws from the class that fits, so a swarm mixing piece lengths shares
one pool without fragmenting it. Retained (free) bytes are capped by
``budget_bytes``; a release that would exceed the budget simply drops
the buffer to the allocator, so the pool can never grow RSS beyond
budget + what is concurrently leased (which the piece pipeline limit
already bounds). Gauges ``bufpool_leased`` / ``bufpool_hit_ratio``
(utils/metrics.py) say whether the pool is actually recycling.

A miss costs what the lease will hold, not what its class could: classes
of ``MAP_CLASS`` (1 MiB) and up are private anonymous mappings, which the
kernel fills with zero pages on first touch, where ``bytearray(size)``
allocates and zero-fills the whole class with the interpreter lock held
(tens of milliseconds for the ingest plane's 64 MiB window, whatever the
blob; a 100 KB push touches 25 pages of it). ``bufpool_miss_bytes_total``
counts the bytes misses asked the allocator for.

Thread-safe: leases happen on the event loop, but releases can arrive
from task done-callbacks racing teardown, and tests drive the pool from
plain sync code.
"""

from __future__ import annotations

import mmap
import threading

MIN_CLASS = 1 << 12  # 4 KiB: below this, pooling costs more than malloc
MAP_CLASS = 1 << 20  # 1 MiB: from here up a miss maps pages, fills none


def _class_for(n: int) -> int:
    size = MIN_CLASS
    while size < n:
        size <<= 1
    return size


def _allocate(size: int) -> bytearray | mmap.mmap:
    """A zeroed, writable buffer of one class. Mappings are MAP_PRIVATE:
    like the heap, and unlike ``mmap.mmap(-1, size)``'s MAP_SHARED, a
    forked worker (p2p/shardpool.py) gets its own copy of a retained
    buffer and not a window into the parent's."""
    if size >= MAP_CLASS:
        return mmap.mmap(
            -1, size, flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
        )
    return bytearray(size)


class Lease:
    """One leased buffer. ``view`` is a length-``n`` writable memoryview
    over the (possibly larger) class-sized backing buffer.
    :meth:`release` is idempotent -- the happy path, the corrupt-piece ban
    path, and teardown callbacks may all race to return one buffer, and
    exactly one return must win (a double return would hand the same
    bytes to two concurrent pieces)."""

    __slots__ = ("_pool", "_buf", "view", "_lock")

    def __init__(
        self, pool: "BufferPool", buf: bytearray | mmap.mmap, n: int
    ):
        self._pool = pool
        self._buf = buf
        self.view = memoryview(buf)[:n]
        self._lock = threading.Lock()

    @property
    def released(self) -> bool:
        return self._buf is None

    def release(self) -> None:
        with self._lock:
            buf, self._buf = self._buf, None
        if buf is None:
            return
        try:
            # Releasing the exporting view makes any use-after-release a
            # loud ValueError instead of a silent read of recycled bytes
            # (which would hash as corruption and ban an innocent peer).
            self.view.release()
        except BufferError:
            # A hash thread still exports the view (cancelled-waiter race:
            # its result is already discarded). The view can't be torn
            # down under it, so DROP the buffer instead of pooling it --
            # a rare lost buffer beats recycling memory a reader holds.
            self._pool._drop(buf)
            return
        self._pool._give_back(buf)


class BufferPool:
    """Process-lifetime pool; one per scheduler, shared by all its conns."""

    def __init__(self, budget_bytes: int = 256 << 20, name: str = "wire"):
        self.name = name
        self._budget = budget_bytes
        self._lock = threading.Lock()
        self._free: dict[int, list[bytearray | mmap.mmap]] = {}
        self._retained = 0
        # Stats (read by tests/bench; rendered as gauges on /metrics).
        self.leased = 0
        self.hits = 0
        self.misses = 0
        self.allocated = 0  # lifetime buffers created (reuse => stays flat)
        self.miss_bytes = 0  # lifetime bytes of those buffers' classes
        # Gauge refs resolved ONCE: this plane exists to shave per-piece
        # CPU, so the per-op metrics update must be three plain sets, not
        # three registry name lookups (metrics.py locks + dict probes).
        from kraken_tpu.utils.metrics import REGISTRY

        self._g_leased = REGISTRY.gauge(
            "bufpool_leased", "Wire payload buffers currently leased"
        )
        self._g_hit = REGISTRY.gauge(
            "bufpool_hit_ratio",
            "Fraction of leases served from the free list",
        )
        self._g_retained = REGISTRY.gauge(
            "bufpool_retained_bytes", "Free bytes retained for reuse"
        )
        self._c_miss_bytes = REGISTRY.counter(
            "bufpool_miss_bytes_total",
            "Bytes allocated for leases the free list could not serve",
        )

    def set_budget(self, budget_bytes: int) -> None:
        """Live-reload surface. Shrinking takes effect lazily: retained
        buffers above the new budget are dropped as they cycle through
        the next release."""
        with self._lock:
            self._budget = budget_bytes

    def lease(self, n: int) -> Lease:
        size = _class_for(n)
        with self._lock:
            free = self._free.get(size)
            if free:
                buf = free.pop()
                self._retained -= size
                self.hits += 1
            else:
                buf = None
                self.misses += 1
                self.allocated += 1
                self.miss_bytes += size
            self.leased += 1
        if buf is None:
            buf = _allocate(size)
            self._record(missed=size)
        else:
            self._record()
        return Lease(self, buf, n)

    def _give_back(self, buf: bytearray | mmap.mmap) -> None:
        size = len(buf)
        with self._lock:
            self.leased -= 1
            keep = self._retained + size <= self._budget
            if keep:
                self._free.setdefault(size, []).append(buf)
                self._retained += size
        if not keep and isinstance(buf, mmap.mmap):
            # Over budget -- back to the allocator, now and not when the
            # collector gets to it. A slice of the lease's view that is
            # still alive (the window worker's frame) keeps the pages
            # mapped until it dies; dropping our reference is enough then.
            try:
                buf.close()
            except BufferError:
                pass
        self._record()

    def _drop(self, buf: bytearray | mmap.mmap) -> None:
        """Lease ends but the buffer is still exported by a reader: count
        the lease back without pooling the bytes (and without unmapping
        them: the mapping goes with its last view)."""
        with self._lock:
            self.leased -= 1
        self._record()

    @property
    def retained_bytes(self) -> int:
        with self._lock:
            return self._retained

    def hit_ratio(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def _record(self, missed: int = 0) -> None:
        with self._lock:
            leased, retained = self.leased, self._retained
            total = self.hits + self.misses
            ratio = self.hits / total if total else 0.0
        self._g_leased.set(leased, pool=self.name)
        self._g_hit.set(ratio, pool=self.name)
        self._g_retained.set(retained, pool=self.name)
        if missed:
            self._c_miss_bytes.inc(missed, pool=self.name)


class SlabRing:
    """Fixed-slot shared-memory slab for the leech-shard plane.

    One anonymous ``MAP_SHARED`` mapping, created in the scheduler
    BEFORE a leech worker forks, so both processes address the same
    pages: the worker's recv pump lands PIECE_PAYLOAD bytes straight
    into a leased slot, and the parent verifies through a zero-copy
    ``view()`` of the very same memory -- the payload never crosses the
    SEQPACKET control channel, only its slot index does.

    Slot sizing follows the bufpool's power-of-two classes (``slot
    class`` = :func:`_class_for` of the largest piece the plane
    accepts); handoff gating in the scheduler keeps any torrent with a
    longer piece length on the main loop. Lease accounting is single-
    owner by design: the WORKER leases and releases (its post-fork copy
    of the free list is authoritative); the parent only reads views and
    mirrors the in-flight count for its leak audit. The lock still
    guards the free list because worker-side releases arrive from the
    control-channel reader while leases happen in conn pumps.
    """

    __slots__ = ("_mm", "slots", "slot_bytes", "_free", "_lock", "leased")

    def __init__(self, slots: int, slot_bytes: int):
        self.slots = max(1, slots)
        self.slot_bytes = _class_for(slot_bytes)
        self._mm = mmap.mmap(-1, self.slots * self.slot_bytes)
        self._free: list[int] = list(range(self.slots))
        self._lock = threading.Lock()
        self.leased = 0

    def lease(self) -> int | None:
        """Claim a free slot index, or None when the ring is full (the
        caller backpressures the conn -- TCP does the rest)."""
        with self._lock:
            if not self._free:
                return None
            self.leased += 1
            return self._free.pop()

    def release(self, slot: int) -> None:
        with self._lock:
            if 0 <= slot < self.slots and slot not in self._free:
                self._free.append(slot)
                self.leased = max(0, self.leased - 1)

    def view(self, slot: int, n: int) -> memoryview:
        """Writable view of ``slot``'s first ``n`` bytes. Valid in both
        processes; the mapping outlives a dead worker, so in-flight
        parent-side views stay readable after a crash."""
        if not 0 <= slot < self.slots or n > self.slot_bytes:
            raise ValueError(f"slot {slot} ({n}B) outside ring")
        off = slot * self.slot_bytes
        return memoryview(self._mm)[off : off + n]

    def close(self) -> None:
        """Best-effort unmap: exported views (a verify batch still
        holding one) keep the mapping alive until they die -- dropping
        the object is always safe."""
        try:
            self._mm.close()
        except (BufferError, ValueError):
            pass
