"""The ``PieceHasher`` interface -- the seam the TPU plane plugs into.

Both hot loops of the system route through this interface (north star in
BASELINE.json):

- origin-side metainfo generation (``origin/metainfogen``): hash every piece
  of every uploaded blob;
- agent-side piece verification (``p2p/storage``): hash every received piece.

Implementations register by name; component YAML selects one via
``hasher: tpu`` / ``hasher: cpu`` exactly like the storage-backend registry
(the same plugin pattern as uber/kraken ``lib/backend`` ``Register(name)``
[UNVERIFIED upstream path]).

The interface is deliberately batch-shaped -- ``hash_pieces`` takes a whole
blob (or a batch of equal-length pieces) and returns an ``[N, 32]`` digest
matrix -- because the TPU implementation amortizes dispatch over thousands
of pieces. A per-piece call would hide the batch axis the hardware needs.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import logging
import os
import sys
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict

import numpy as np

from kraken_tpu.utils import trace
from kraken_tpu.utils.metrics import REGISTRY

DIGEST_SIZE = 32

_log = logging.getLogger("kraken.hasher")


class HashPool:
    """Worker threads for the HOST piece-hash path (`hash_workers`).

    Piece hashing is embarrassingly parallel and ``hashlib`` releases
    the GIL for large buffers, so N workers hash N pieces genuinely
    concurrently -- the multi-core lever the serial loop left on the
    table (ingest was hash-bound at 0.365 GB/s on one core; VERDICT r5
    missing #2). The running blob digest stays OFF this pool: it is
    order-dependent and remains the stated serial term of the ingest
    scaling model (PERF.md "parallel host hashing").

    Occupancy and queue-depth gauges publish at every task edge (submit/
    start/finish -- a few per piece or per window shard, so the metric
    cost is noise next to a 4 MiB sha pass).

    Known scheduling limitation: the pool is one FIFO shared by the live
    stream tier and the background re-read passes (generate() on tier
    miss / reseed / scrub, dedup chunk hashing), so a stream piece
    submitted behind a ~window/workers-sized generate() shard waits for
    it (order ~100 ms). Those re-read passes are rare on a healthy
    origin; if they become foreground work, a second pool (distinct
    hash_workers instance) isolates them.
    """

    def __init__(self, workers: int, name: str = "cpu"):
        if workers < 1:
            raise ValueError(f"hash pool needs >= 1 worker: {workers}")
        self.workers = workers
        self.name = name
        self._ex = ThreadPoolExecutor(
            workers, thread_name_prefix=f"hashpool-{name}"
        )
        self._lock = threading.Lock()
        self._running = 0
        self._queued = 0
        self._publish()  # gauges visible on /metrics from construction

    def _publish(self) -> None:
        from kraken_tpu.utils.metrics import record_hash_pool_metrics

        record_hash_pool_metrics(
            self.name, self.workers, self._running, self._queued
        )

    def submit(self, fn: Callable, *args) -> Future:
        with self._lock:
            self._queued += 1
            self._publish()

        def run():
            with self._lock:
                self._queued -= 1
                self._running += 1
                self._publish()
            try:
                return fn(*args)
            finally:
                with self._lock:
                    self._running -= 1
                    self._publish()

        # The caller's context (its trace span) rides to the worker: spans
        # and device sections opened there join the submitter's trace.
        return self._ex.submit(contextvars.copy_context().run, run)

    def run_sharded(self, n: int, worker: Callable[[int, int], None]) -> None:
        """Run ``worker(lo, hi)`` over ``[0, n)`` split into at most
        ``self.workers`` contiguous shards, blocking until all finish.
        The split is contiguous so each worker walks memory sequentially
        (pieces are adjacent in the source buffer)."""
        shards = min(self.workers, n)
        bounds = [k * n // shards for k in range(shards + 1)]
        futs = [
            self.submit(worker, bounds[k], bounds[k + 1])
            for k in range(shards)
        ]
        for f in futs:
            f.result()


def record_hash_metrics(hasher: str, nbytes: int, pieces: int) -> None:
    """Cumulative byte/piece counters of the piece-hash plane, labeled by
    hasher. Rate and occupancy derive from the device-section counters
    below (``hasher_device_*``), which count every dispatch."""
    REGISTRY.counter(
        "hasher_bytes_total", "Bytes hashed through the piece-hash plane"
    ).inc(nbytes, hasher=hasher)
    REGISTRY.counter(
        "hasher_pieces_total", "Pieces hashed through the piece-hash plane"
    ).inc(pieces, hasher=hasher)


def profiler_annotation(name: str, **args):
    """A ``jax.profiler.TraceAnnotation``: a named host span in the
    profiler's own file, free when no capture runs. A process that never
    imported jax (cpu hasher) has no profiler to tell, and pays no import.
    Enter and exit on one thread, with no ``await`` between."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **args)


def sha_blocks(length: int) -> int:
    """64-byte blocks SHA-256 runs over a message of ``length`` bytes,
    its padding included."""
    return (length + 8) // 64 + 1


class DeviceLedger:
    """Who holds the chip, and who waits for it.

    Every host-side path that enqueues device work and then waits for its
    result runs inside one :meth:`section`. One chip serves sections in
    the order they were enqueued and each section blocks its thread until
    its result is back, so one ``last_done`` under a small lock splits a
    section's wall into time WAITED behind earlier sections and time the
    chip was HELD for this one::

        start = max(t_enter, last_done); held = now - start
        waited = start - t_enter;        last_done = now

    A section that enters with none open books the time since
    ``last_done`` as the device's IDLE gap, to its own labels (the
    process's first section books none): idle / (idle + held) is the
    device's idle share as the host sees it.

    Held intervals never overlap, so their sum never passes the wall. It
    is an estimate with two known errors: host<->device copies and
    dispatch latency are booked as held when the device was idle before,
    and two threads that enqueue within microseconds of each other may be
    booked in the other order (PERF.md section 3). The clock is
    ``time.monotonic``, the one ``bench_trace_open`` is read on.

    One per process, like the metric REGISTRY (``DEVICE_LEDGER``); tests
    build their own over a fake clock.
    """

    def __init__(self, clock=time.monotonic, registry=REGISTRY):
        self._clock = clock
        self._lock = threading.Lock()
        self._last_done = float("-inf")
        self._open: dict[int, float] = {}  # id(section) -> t_enter
        self._seen: set[tuple] = set()
        self._sections = registry.counter(
            "hasher_device_sections_total",
            "Device sections: one enqueue-then-wait of device work",
        )
        self._rows = registry.counter(
            "hasher_device_rows_total",
            "Rows dispatched in device sections, padding rows included",
        )
        self._blocks = registry.counter(
            "hasher_device_blocks_total",
            "Block slots dispatched in device sections (rows x block axis,"
            " padding included)",
        )
        self._useful = registry.counter(
            "hasher_device_useful_blocks_total",
            "Block slots of device sections that held real input",
        )
        self._held = registry.counter(
            "hasher_device_held_seconds_total",
            "Seconds the device was held by sections (estimate: disjoint"
            " intervals, in order of completion)",
        )
        self._wait = registry.counter(
            "hasher_device_wait_seconds_total",
            "Seconds sections waited behind earlier sections",
        )
        self._first = registry.counter(
            "hasher_device_first_use_total",
            "Sections that met a (kernel, shape) for the first time in this"
            " process: the ones that trace and compile",
        )
        self._first_s = registry.counter(
            "hasher_device_first_use_seconds_total",
            "Held seconds of first-use sections",
        )
        self._idle = registry.counter(
            "hasher_device_idle_seconds_total",
            "Seconds from the last section's end to a section's enter with"
            " none open, booked to the entering section: the device's idle"
            " time as the host sees it",
        )

    def section(
        self, purpose: str, kernel: str, *, rows: int, blocks: int,
        useful_blocks: int, payload_bytes: int, shape: tuple | None = None,
    ) -> "DeviceSection":
        """``purpose``: whose work (``piece`` the acknowledged ingest path,
        ``chunk`` the dedup pass, ``verify`` the agent, ``sketch``,
        ``cdc``). ``kernel``: a stable name. ``rows`` x ``blocks``: the
        dispatch as the device gets it, padding included;
        ``useful_blocks``: the sum of each row's real block count.
        ``shape`` keys first use where (rows, blocks) does not (several
        sub-batches in one section)."""
        return DeviceSection(
            self, purpose, kernel, rows, blocks, useful_blocks,
            payload_bytes, shape if shape is not None else (rows, blocks),
        )

    def held_seconds(self) -> float:
        """Held seconds up to now, over every purpose and kernel: the
        counter, which grows when a section ends, plus what the sections
        still open have held so far (the chip is held since the later of
        the last section's end and the earliest open section's start).
        Two readings a fraction of a second apart differ by the time the
        chip was held between them, not by whole sections."""
        with self._lock:
            total = self._held.total()
            if self._open:
                total += max(0.0, self._clock() - max(
                    self._last_done, min(self._open.values())
                ))
        return total


class DeviceSection:
    """Context manager returned by :meth:`DeviceLedger.section`. Enter it
    immediately before the first enqueue (after host-side padding), leave
    it when the result is on the host."""

    __slots__ = (
        "_ledger", "_labels", "_rows", "_blocks", "_useful", "_payload",
        "_shape", "_span", "_sp", "_annotation", "_t_enter",
        "held_s", "waited_s",
    )

    def __init__(self, ledger, purpose, kernel, rows, blocks, useful,
                 payload, shape):
        self._ledger = ledger
        self._labels = {"purpose": purpose, "kernel": kernel}
        self._rows = rows
        self._blocks = blocks
        self._useful = useful
        self._payload = payload
        self._shape = shape

    def __enter__(self) -> "DeviceSection":
        purpose, kernel = self._labels["purpose"], self._labels["kernel"]
        self._span = trace.span(
            "hasher.device", purpose=purpose, kernel=kernel,
            rows=self._rows, blocks=self._blocks,
            payload_bytes=self._payload,
        )
        self._sp = self._span.__enter__()
        # The profiler's own file names whose section each stretch of
        # device time belongs to.
        self._annotation = profiler_annotation(
            f"kraken.device.{purpose}.{kernel}",
            rows=self._rows, blocks=self._blocks,
        )
        self._annotation.__enter__()
        ledger = self._ledger
        with ledger._lock:
            now = self._t_enter = ledger._clock()
            idle = (now - ledger._last_done
                    if not ledger._open and ledger._last_done > float("-inf")
                    else 0.0)
            ledger._open[id(self)] = now
        if idle > 0:
            ledger._idle.inc(idle, **self._labels)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        ledger = self._ledger
        key = (self._labels["kernel"], self._shape)
        with ledger._lock:
            # Read under the lock: ``now`` then never runs backwards from
            # one section's exit to the next, so held is never negative.
            now = ledger._clock()
            start = max(self._t_enter, ledger._last_done)
            ledger._last_done = now
            del ledger._open[id(self)]
            first = key not in ledger._seen
            if first:
                ledger._seen.add(key)
        held = self.held_s = now - start
        self.waited_s = start - self._t_enter
        labels = self._labels
        ledger._sections.inc(**labels)
        ledger._rows.inc(self._rows, **labels)
        ledger._blocks.inc(self._rows * self._blocks, **labels)
        ledger._useful.inc(self._useful, **labels)
        ledger._held.inc(held, **labels)
        ledger._wait.inc(self.waited_s, **labels)
        if first:
            ledger._first.inc(**labels)
            ledger._first_s.inc(held, **labels)
            _log.info(
                "device section first use",
                extra={**labels, "shape": repr(self._shape),
                       "held_s": round(held, 6)},
            )
        self._annotation.__exit__(exc_type, exc, tb)
        if self._sp is not None:
            # start_mono beside the span's wall-clock start_ts: the clock
            # the profiler's annotations and the harness are read on.
            self._sp.set(
                start_mono=round(self._t_enter, 6), held_s=round(held, 6),
                waited_s=round(self.waited_s, 6),
            )
        return self._span.__exit__(exc_type, exc, tb)


DEVICE_LEDGER = DeviceLedger()
device_section = DEVICE_LEDGER.section


class PieceHasher:
    """Batched SHA-256 over the pieces of a blob.

    Implementations must be safe to share across threads/tasks.
    """

    name = "abstract"
    # Host hash-worker pool, when the implementation has one (the cpu
    # hasher with hash_workers >= 1). Callers that can feed independent
    # pieces concurrently (the origin's stream-time tier) use it
    # directly; None = strictly serial hashing.
    pool: HashPool | None = None

    def devices(self) -> list:
        """The JAX devices this hasher places its work on; none for a
        hasher that runs on the host."""
        return []

    def device_info(self) -> dict | None:
        """``{"platform", "device_kind", "count"}`` of :meth:`devices`, as
        JAX reports them; None for a hasher that runs on the host.
        Printed on the component's READY line and exported as
        ``hasher_device_info``, so a deployment (and chip_smoke.py) can
        see where ``hasher: tpu`` really landed."""
        devs = self.devices()
        if not devs:
            return None
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs),
        }

    def hash_pieces(self, data: bytes | memoryview, piece_length: int) -> np.ndarray:
        """Split ``data`` into ``piece_length`` pieces (last may be short)
        and return the SHA-256 of each as a ``[num_pieces, 32] uint8``
        array. A zero-length blob returns ``[0, 32]``."""
        raise NotImplementedError

    def hash_batch(
        self, pieces: list[bytes | memoryview], purpose: str = "verify"
    ) -> np.ndarray:
        """Hash a list of arbitrary-length pieces -> ``[len(pieces), 32]``.

        Used by the agent verify path, where received pieces arrive out of
        order and are batched briefly before verification, and by the
        origin's dedup pass (``purpose="chunk"``): the purpose labels the
        call's device sections (:class:`DeviceLedger`).
        """
        raise NotImplementedError


class CPUPieceHasher(PieceHasher):
    """Reference implementation on hashlib. Also the golden oracle for the
    TPU plane's tests (crypto hashes admit no tolerance).

    ``workers >= 1`` hashes independent pieces through a :class:`HashPool`
    (hashlib drops the GIL, so workers scale with cores); ``workers <= 0``
    is the strictly serial pre-pool path -- the registry default, and the
    oracle the pooled path is parity-tested against. Digests are
    bit-identical either way: sharding only reorders WHICH thread hashes
    a piece, never the piece boundaries.
    """

    name = "cpu"

    def __init__(self, workers: int = 0):
        # Pool label carries the worker count: two pools in one process
        # (origin hash_workers=4 + agent hash_workers=2) must not clobber
        # each other's gauges.
        self.pool = (
            HashPool(workers, name=f"cpu/{workers}") if workers >= 1 else None
        )

    def hash_pieces(self, data: bytes | memoryview, piece_length: int) -> np.ndarray:
        if piece_length <= 0:
            raise ValueError(f"piece_length must be positive: {piece_length}")
        view = memoryview(data)
        n = (len(view) + piece_length - 1) // piece_length
        out = np.empty((n, DIGEST_SIZE), dtype=np.uint8)

        def run(lo: int, hi: int) -> None:
            # One row-matrix write per SHARD, not per piece: the digest
            # list + join keeps the GIL-held numpy work out of the inner
            # loop, which measures ~5% under 2-thread contention. Rows
            # are disjoint, so concurrent shard writes never conflict.
            digs = [
                hashlib.sha256(
                    view[i * piece_length : (i + 1) * piece_length]
                ).digest()
                for i in range(lo, hi)
            ]
            out[lo:hi] = np.frombuffer(
                b"".join(digs), dtype=np.uint8
            ).reshape(-1, DIGEST_SIZE)

        # The pool only helps a BLOCKING batch call when it can shard
        # (workers >= 2): a 1-worker pool would move the whole pass to
        # another thread and wait -- pure overhead. (A 1-worker pool
        # still earns its keep on the stream tier, where piece hashing
        # OVERLAPS the serial blob digest via submit().)
        if not n:
            return out
        full = sha_blocks(piece_length)
        with device_section(
            "piece", "hashlib", rows=n, blocks=full,
            useful_blocks=(n - 1) * full
            + sha_blocks(len(view) - (n - 1) * piece_length),
            payload_bytes=len(view),
        ):
            if self.pool is None or self.pool.workers < 2 or n <= 1:
                run(0, n)
            else:
                self.pool.run_sharded(n, run)
        record_hash_metrics(self.name, len(view), n)
        return out

    def hash_batch(
        self, pieces: list[bytes | memoryview], purpose: str = "verify"
    ) -> np.ndarray:
        out = np.empty((len(pieces), DIGEST_SIZE), dtype=np.uint8)

        def run(lo: int, hi: int) -> None:
            digs = [hashlib.sha256(pieces[i]).digest() for i in range(lo, hi)]
            out[lo:hi] = np.frombuffer(
                b"".join(digs), dtype=np.uint8
            ).reshape(-1, DIGEST_SIZE)

        if not pieces:
            return out
        lengths = [len(memoryview(p)) for p in pieces]
        with device_section(
            purpose, "hashlib", rows=len(pieces),
            blocks=sha_blocks(max(lengths)),
            useful_blocks=sum(map(sha_blocks, lengths)),
            payload_bytes=sum(lengths),
        ):
            if self.pool is None or self.pool.workers < 2 or len(pieces) <= 1:
                run(0, len(pieces))
            else:
                self.pool.run_sharded(len(pieces), run)
        return out


_REGISTRY: Dict[str, Callable[[], PieceHasher]] = {}
_INSTANCES: Dict[str, PieceHasher] = {}


def register_hasher(name: str, factory: Callable[[], PieceHasher]) -> None:
    _REGISTRY[name] = factory


def _place_compile_cache() -> None:
    """Give JAX's persistent compilation cache a home before the first
    device hasher jits anything: each Mosaic SHA shape costs seconds to
    compile, and origin and agent would otherwise pay it on every start.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own business and
    nothing is set here; otherwise the cache is ``.jax_cache`` beside the
    package -- one fixed path, because the path is part of the cache key
    and a directory that moves never hits."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))),
            ".jax_cache",
        ),
    )


def _export_device(hasher: PieceHasher) -> None:
    """Put a device hasher's identity and its devices' peak memory on
    ``/metrics``. The peak is read when a scrape renders (one
    ``memory_stats()`` call a device), never on the dispatch path."""
    info = hasher.device_info()
    if info is None:
        return
    REGISTRY.gauge(
        "hasher_device_info",
        "The devices the piece hasher places its work on (value 1)",
    ).set(1, platform=info["platform"], kind=info["device_kind"],
          count=info["count"])
    peak = REGISTRY.gauge(
        "hasher_device_memory_peak_bytes",
        "peak_bytes_in_use of the hasher's devices (the largest), read at"
        " scrape time; 0 where the backend reports none",
    )

    def read_peak() -> None:
        peak.set(max(
            int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for dev in hasher.devices()
        ), hasher=hasher.name)

    REGISTRY.add_scrape_hook(read_peak)


def get_hasher(name: str = "cpu", workers: int = 0) -> PieceHasher:
    """Resolve a hasher by registry name (``cpu``, ``tpu``,
    ``tpu-sharded`` -- the last fans the piece batch across every local
    chip via shard_map).

    Instances are cached: TPU hasher construction compiles kernels, so the
    origin and agent share one instance per process.

    ``workers`` (the YAML ``hash_workers`` knob) applies only to the cpu
    hasher: ``workers >= 1`` returns a pooled instance cached per worker
    count, so an origin and an agent configured alike share one pool per
    process. Device hashers ignore it -- their parallelism is the batch
    axis, not host threads.
    """
    if name == "cpu" and workers >= 1:
        key = f"cpu/{workers}"
        if key not in _INSTANCES:
            _INSTANCES[key] = CPUPieceHasher(workers=workers)
        return _INSTANCES[key]
    if name not in _INSTANCES:
        if name not in _REGISTRY:
            # Importing the plane registers its hashers; deferred so that
            # pure-CPU components never pay the JAX import.
            if name in ("tpu", "tpu-sharded"):
                _place_compile_cache()
            if name == "tpu":
                import kraken_tpu.ops.sha256  # noqa: F401
            elif name == "tpu-sharded":
                import kraken_tpu.parallel.hashplane  # noqa: F401
        try:
            factory = _REGISTRY[name]
        except KeyError:
            raise KeyError(
                f"unknown hasher {name!r}; registered: {sorted(_REGISTRY)}"
            ) from None
        _INSTANCES[name] = factory()
        _export_device(_INSTANCES[name])
    return _INSTANCES[name]


register_hasher("cpu", CPUPieceHasher)
