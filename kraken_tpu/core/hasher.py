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

import hashlib
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict

import numpy as np

DIGEST_SIZE = 32


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

        return self._ex.submit(run)

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


def record_hash_metrics(
    hasher: str, nbytes: int, pieces: int, seconds: float,
    occupancy: float = 1.0,
) -> None:
    """North-star gauges (SURVEY.md SS6): per-dispatch GB/s and batch
    occupancy, plus cumulative byte/piece counters, labeled by hasher."""
    from kraken_tpu.utils.metrics import REGISTRY

    REGISTRY.counter(
        "hasher_bytes_total", "Bytes hashed through the piece-hash plane"
    ).inc(nbytes, hasher=hasher)
    REGISTRY.counter(
        "hasher_pieces_total", "Pieces hashed through the piece-hash plane"
    ).inc(pieces, hasher=hasher)
    if seconds > 0:
        REGISTRY.gauge(
            "hasher_last_gbps", "Throughput of the last hash_pieces call"
        ).set(nbytes / seconds / 1e9, hasher=hasher)
    REGISTRY.gauge(
        "hasher_batch_occupancy",
        "Useful rows / dispatched rows in the last hash_pieces call",
    ).set(occupancy, hasher=hasher)


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

    def device_info(self) -> dict | None:
        """``{"platform", "device_kind", "count"}`` of the devices this
        hasher places its work on, as JAX reports them; None for a
        hasher that runs on the host. Printed on the component's READY
        line, so a deployment (and chip_smoke.py) can see where
        ``hasher: tpu`` really landed."""
        return None

    def hash_pieces(self, data: bytes | memoryview, piece_length: int) -> np.ndarray:
        """Split ``data`` into ``piece_length`` pieces (last may be short)
        and return the SHA-256 of each as a ``[num_pieces, 32] uint8``
        array. A zero-length blob returns ``[0, 32]``."""
        raise NotImplementedError

    def hash_batch(self, pieces: list[bytes | memoryview]) -> np.ndarray:
        """Hash a list of arbitrary-length pieces -> ``[len(pieces), 32]``.

        Used by the agent verify path, where received pieces arrive out of
        order and are batched briefly before verification.
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
        start = time.perf_counter()
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
        if self.pool is None or self.pool.workers < 2 or n <= 1:
            if n:
                run(0, n)
        else:
            self.pool.run_sharded(n, run)
        if n:
            record_hash_metrics(
                self.name, len(view), n, time.perf_counter() - start
            )
        return out

    def hash_batch(self, pieces: list[bytes | memoryview]) -> np.ndarray:
        out = np.empty((len(pieces), DIGEST_SIZE), dtype=np.uint8)

        def run(lo: int, hi: int) -> None:
            digs = [hashlib.sha256(pieces[i]).digest() for i in range(lo, hi)]
            out[lo:hi] = np.frombuffer(
                b"".join(digs), dtype=np.uint8
            ).reshape(-1, DIGEST_SIZE)

        if self.pool is None or self.pool.workers < 2 or len(pieces) <= 1:
            if pieces:
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
    return _INSTANCES[name]


register_hasher("cpu", CPUPieceHasher)
