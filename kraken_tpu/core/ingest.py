"""Pipelined zero-copy ingest plane: upload spool -> device hash.

The feed path used to be serial -- read the whole window, then hash it,
then read the next -- which leaves the chip idle while the host reads and
the host idle while the chip hashes. This module turns that into a
multi-window stream:

    read -> transfer -> hash        (per window)

with ``windows_in_flight`` windows overlapped: while window k hashes on
the device (or the host pool), window k+1 is being read into its own
staging buffer. Staging buffers are bufpool-backed (``utils/bufpool``)
and reused across windows -- the read lands bytes DIRECTLY in the buffer
the transfer/hash consumes (``readinto`` / stream-chunk copy), which is
the only host copy the window ever takes.

Stage semantics per window:

- **read**: filling the staging buffer (spool ``readinto`` on the
  re-generate path; request-body chunk copy on the stream path).
- **transfer**: ``jax.device_put`` of the window onto the mesh (hashers
  with ``stage_window`` only; the buffer is free for reuse as soon as
  the put returns, which is the donation point of the double-buffer
  scheme).
- **hash**: the device dispatch + digest readback, or the CPU HashPool
  piece pass -- the automatic fallback when no device hasher is
  configured.

Every window observes ``ingest_stage_seconds{stage}`` and the per-upload
stage walls land on the ingest trace span (origin/server.py). Three more
stages time what a commit waits for outside the window's own work and stay
out of ``overlap_ratio``: **queue** (``submit`` until a worker picks the
window up: the one executor every upload of the process shares), **join**
and **publish** (origin/server.py: the commit's wait for its last window,
then metainfo adoption and the post-commit fan-out). Digests are
bit-identical to the serial oracle by construction: pipelining reorders
WHEN a piece is hashed, never piece boundaries.
"""

from __future__ import annotations

import contextvars
import dataclasses
import logging
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Optional

import numpy as np

from kraken_tpu.core.hasher import (
    DIGEST_SIZE,
    PieceHasher,
    profiler_annotation,
)
from kraken_tpu.utils import failpoints
from kraken_tpu.utils.pushsteps import push_step, stepped

_log = logging.getLogger("kraken.ingest")

# Stage walls span ~100 us (a reshape) to ~10 s (a multi-GiB window on a
# cold page cache): wider-than-default log-spaced buckets.
STAGE_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0,
)


def record_stage(stage: str, seconds: float) -> None:
    """One window's (or commit's) wall for one pipeline stage."""
    from kraken_tpu.utils.metrics import REGISTRY

    REGISTRY.histogram(
        "ingest_stage_seconds",
        "Per-window wall of each ingest pipeline stage",
        buckets=STAGE_BUCKETS,
    ).observe(seconds, stage=stage)


class timed_stage:
    """``with timed_stage("hash", bill):`` -- one clock reading serves the
    histogram, the session's stage wall (``bill(stage, seconds)``) and a
    ``kraken.<plane>.<stage>`` annotation in the profiler's file. A stage
    that raises is not billed. One thread, no ``await`` inside. The
    stage is also a step of the push-step ledger (utils/pushsteps.py),
    ``<plane>.<stage>`` unless ``step`` names it: that is where its cpu
    clock goes, and a raise is booked there."""

    __slots__ = (
        "_stage", "_bill", "_plane", "_annotation", "_step", "_t0", "seconds",
    )

    def __init__(self, stage: str, bill=record_stage, plane: str = "ingest",
                 step: str | None = None):
        self._stage = stage
        self._bill = bill
        self._plane = plane
        self._step = push_step(step or f"{plane}.{stage}")
        self.seconds = 0.0

    def __enter__(self) -> "timed_stage":
        self._annotation = profiler_annotation(
            f"kraken.{self._plane}.{self._stage}"
        )
        self._annotation.__enter__()
        self._step.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            self.seconds = time.perf_counter() - self._t0
            self._bill(self._stage, self.seconds)
        self._step.__exit__(exc_type, exc, tb)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """The YAML ``ingest:`` section (origin; SIGHUP live-reloads). Knob
    table + rollout runbook in docs/OPERATIONS.md "Pipelined ingest"."""

    # Bytes per pipeline window (floored to whole pieces at run time; a
    # window always holds >= 1 piece). Bigger windows amortize dispatch,
    # smaller windows bound staging RAM: peak staging is roughly
    # window_bytes * windows_in_flight.
    window_bytes: int = 64 * 1024 * 1024
    # Windows concurrently in flight (read overlapping transfer/hash).
    # 2 = classic double buffering, the shipped default; 1 degenerates
    # to the serial path (useful to price the overlap).
    windows_in_flight: int = 2
    # Resumable upload sessions: journal per-upload durable progress to a
    # ``upload/<uid>.session`` sidecar so a crashed/drained origin
    # re-adopts live sessions after restart and clients resume from the
    # journaled offset instead of retrying from zero. Shipped ON (pure
    # robustness; one tiny sidecar write per flush batch). On agents the
    # same knob gates keeping resumable partial state across a restart.
    resume: bool = True
    # Publish metainfo and seed the blob from its upload spool as soon as
    # every piece is hashed -- strictly BEFORE the commit rename -- so
    # agents fan out behind the upload front. Shipped OFF (rollout
    # runbook in docs/OPERATIONS.md "Resumable ingest &
    # serve-while-ingest").
    serve_while_ingest: bool = False

    def __post_init__(self):
        if self.window_bytes < 1 << 20:
            raise ValueError(
                f"ingest.window_bytes must be >= 1 MiB: {self.window_bytes}"
            )
        if self.windows_in_flight < 1:
            raise ValueError(
                "ingest.windows_in_flight must be >= 1: "
                f"{self.windows_in_flight}"
            )

    @classmethod
    def from_dict(cls, doc: dict | None) -> "IngestConfig":
        doc = dict(doc or {})
        allowed = {f.name for f in dataclasses.fields(cls)}
        unknown = set(doc) - allowed
        if unknown:
            raise ValueError(f"unknown ingest config keys: {sorted(unknown)}")
        return cls(**doc)


class IngestPipeline:
    """Window-stream executor over one PieceHasher.

    Thread-safe; one pipeline per origin process, shared by the stream
    path (origin/server.py _UploadDigest) and the re-generate path
    (origin/metainfogen.py). SIGHUP swaps the config via :meth:`apply` --
    in-flight sessions keep their birth config, new sessions see the new
    knobs.
    """

    def __init__(self, hasher: PieceHasher, config: IngestConfig | None = None):
        from kraken_tpu.utils.bufpool import BufferPool

        self.hasher = hasher
        self.config = config or IngestConfig()
        self._lock = threading.Lock()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._executor_width = 0
        # Staging buffers: retained budget sized to the steady state
        # (windows_in_flight leases cycling) so the pool serves every
        # window after the first lap without allocator traffic. Every
        # session leases a whole window for its first bytes, and more
        # concurrent sessions than retained windows miss: a window is
        # mapped, not filled (utils/bufpool.py), so a miss costs the
        # pages the blob lands in and a 1 KiB push does not pay for 64 MiB.
        self._bufpool = BufferPool(
            budget_bytes=self.config.window_bytes
            * (self.config.windows_in_flight + 1),
            name="ingest",
        )

    def apply(self, config: IngestConfig) -> None:
        """Live config swap (SIGHUP). Cheap when nothing changed."""
        with self._lock:
            old, self.config = self.config, config
            if old == config:
                return
            self._bufpool.set_budget(
                config.window_bytes * (config.windows_in_flight + 1)
            )
            if self._executor is not None and (
                self._executor_width != config.windows_in_flight
            ):
                # Old executor drains its queued windows and exits; new
                # sessions get a fresh one at the new width.
                self._executor.shutdown(wait=False)
                self._executor = None

    def _get_executor(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._executor is None:
                self._executor_width = self.config.windows_in_flight
                self._executor = ThreadPoolExecutor(
                    self._executor_width, thread_name_prefix="ingest"
                )
            return self._executor

    def session(self, piece_length: int) -> "IngestSession":
        if piece_length <= 0:
            raise ValueError(f"piece_length must be positive: {piece_length}")
        return IngestSession(self, piece_length)


class IngestSession:
    """One blob's window stream through the pipeline.

    Caller protocol (any ONE thread, off-loop):

        ses = pipeline.session(piece_length)
        while bytes remain:
            buf = ses.begin_window()     # memoryview to fill
            n = fill(buf)                # readinto / chunk copies
            ses.submit(n)                # queues transfer/hash
        digests = ses.finish()           # [N, 32] uint8, piece order

    ``submit`` blocks once ``windows_in_flight`` windows are queued or
    running -- that backpressure IS the double-buffer bound. Only the
    LAST submitted window may be short or ragged.
    """

    def __init__(self, pipeline: IngestPipeline, piece_length: int):
        cfg = pipeline.config
        self.pipeline = pipeline
        self.piece_length = piece_length
        pieces = max(1, cfg.window_bytes // piece_length)
        self.window_bytes = pieces * piece_length
        self._sem = threading.Semaphore(cfg.windows_in_flight)
        self._futs: list[Future] = []
        self._lease = None
        self._read_t0 = 0.0
        self._t0: Optional[float] = None
        self._done = False
        # Sticky device->host degradation flag: set by the first window
        # whose device path faults; later windows route straight to the
        # host pass. Benign cross-thread bool.
        self._fell_back = False
        self.stage_seconds: dict[str, float] = dict.fromkeys(
            ("read", "transfer", "hash"), 0.0
        )
        # Submit -> a worker picks the window up, summed over windows.
        # Not a stage of the window's own work: out of overlap_ratio.
        self.queue_seconds = 0.0
        self.windows = 0
        self.wall_seconds = 0.0

    # -- caller side -----------------------------------------------------

    def begin_window(self) -> memoryview:
        """Lease the next staging buffer. The read wall for the window is
        measured from here to :meth:`submit`."""
        if self._lease is not None:
            raise RuntimeError("previous window was never submitted")
        if failpoints.fire("ingest.window.read"):
            # Staging-read fault (torn spool, bad request body): fired
            # BEFORE the semaphore/lease so nothing needs returning; the
            # caller's abort() path is what the site exists to exercise.
            raise failpoints.FailpointError("ingest.window.read")
        # Blocks while windows_in_flight windows are queued/running: the
        # NEXT read must not race ahead of the staging budget.
        self._sem.acquire()
        if self._t0 is None:
            self._t0 = time.perf_counter()
        self._lease = self.pipeline._bufpool.lease(self.window_bytes)
        self._read_t0 = time.perf_counter()
        return self._lease.view[: self.window_bytes]

    def submit(self, nbytes: int) -> None:
        """Queue the filled prefix of the current staging buffer."""
        if self._lease is None:
            raise RuntimeError("submit without begin_window")
        if not 0 <= nbytes <= self.window_bytes:
            raise ValueError(f"submit: {nbytes} outside window")
        lease, self._lease = self._lease, None
        # No profiler annotation for read: begin_window and submit may run
        # on different threads (one PATCH flush each).
        t_submit = time.perf_counter()
        read_s = t_submit - self._read_t0
        self.stage_seconds["read"] += read_s
        record_stage("read", read_s)
        self.windows += 1
        if nbytes == 0:
            lease.release()
            self._sem.release()
            return
        # The submitter's context rides along: the window's spans and
        # device sections are children of the request that submitted it.
        fut = self.pipeline._get_executor().submit(
            contextvars.copy_context().run,
            self._process, lease, nbytes, t_submit,
        )
        self._futs.append(fut)

    def finish(self) -> np.ndarray:
        """Wait for every window; concatenated digests in piece order."""
        if self._lease is not None:  # begin_window with no submit
            self._lease.release()
            self._lease = None
            self._sem.release()
        try:
            parts = [f.result() for f in self._futs]
        finally:
            self._done = True
        self.wall_seconds = (
            time.perf_counter() - self._t0 if self._t0 is not None else 0.0
        )
        from kraken_tpu.utils.metrics import REGISTRY

        REGISTRY.counter(
            "ingest_windows_total",
            "Windows processed by the pipelined ingest plane",
        ).inc(self.windows, hasher=self.pipeline.hasher.name)
        if self.wall_seconds > 0:
            REGISTRY.gauge(
                "ingest_last_overlap_ratio",
                "sum(stage walls) / wall of the last ingest session "
                "(>1 = stages overlapped)",
            ).set(self.overlap_ratio(), hasher=self.pipeline.hasher.name)
        if not parts:
            return np.empty((0, DIGEST_SIZE), dtype=np.uint8)
        return np.concatenate(parts) if len(parts) > 1 else parts[0]

    def abort(self) -> None:
        """Stop trusting this session: wait out in-flight windows (their
        leases must return to the pool) and drop the results. Every
        staging lease provably returns: the un-submitted window's lease
        is released here, submitted windows release theirs in
        ``_process``'s finally -- joined below before the drop."""
        hit = failpoints.fire("ingest.abort")
        if hit and hit.delay_s:
            # Chaos: stretch the abort window so teardown races (a PATCH
            # failing while windows are still hashing) become reachable.
            time.sleep(hit.delay_s)
        if self._lease is not None:
            self._lease.release()
            self._lease = None
            self._sem.release()
        for f in self._futs:
            try:
                f.result()
            except Exception:  # kt-lint: disable=bare-except  # aborting: window results AND their failures are discarded by contract -- the caller falls back to the verifying re-read pass
                pass
        self._futs = []
        self._done = True

    def completed_digest_prefix(self) -> np.ndarray:
        """Digests of the in-order prefix of windows already hashed --
        non-blocking (stops at the first pending window). The resumable-
        upload journal tick reads this on the PATCH flush thread, so it
        must never wait on a device hash wall."""
        out = []
        for f in self._futs:
            if not f.done() or f.exception() is not None:
                break
            out.append(f.result())
        if not out:
            return np.empty((0, DIGEST_SIZE), dtype=np.uint8)
        return np.concatenate(out) if len(out) > 1 else out[0]

    def digest_prefix(self, n_pieces: int) -> np.ndarray:
        """First ``n_pieces`` digests, blocking on the windows that hold
        them (session-adoption replay verify). Window faults propagate --
        the caller treats the session as unadoptable."""
        out, got = [], 0
        for f in self._futs:
            if got >= n_pieces:
                break
            arr = f.result()
            out.append(arr)
            got += arr.shape[0]
        if not out:
            return np.empty((0, DIGEST_SIZE), dtype=np.uint8)
        cat = np.concatenate(out) if len(out) > 1 else out[0]
        return cat[:n_pieces]

    def overlap_ratio(self) -> float:
        """sum-of-stage-walls / session wall. 1.0 = fully serial; toward
        ``windows_in_flight`` = stages genuinely overlapped."""
        if self.wall_seconds <= 0:
            return 1.0
        return sum(self.stage_seconds.values()) / self.wall_seconds

    # -- worker side -----------------------------------------------------

    def _bill(self, stage: str, seconds: float) -> None:
        self.stage_seconds[stage] += seconds
        record_stage(stage, seconds)

    @stepped("ingest.window")
    def _process(self, lease, nbytes: int, t_submit: float) -> np.ndarray:
        queue_s = time.perf_counter() - t_submit
        self.queue_seconds += queue_s
        record_stage("queue", queue_s)
        try:
            view = lease.view[:nbytes]
            plen = self.piece_length
            if self._fell_back:
                # A previous window already tripped the device fallback:
                # the rest of the stream stays on the host path (a chip
                # that faulted once is not re-trusted mid-blob).
                return self._host_window(view, plen)
            try:
                if failpoints.fire("origin.ingest.device_fail"):
                    raise failpoints.FailpointError(
                        "origin.ingest.device_fail"
                    )
                return self._hasher_window(view, plen)
            except Exception as e:
                # Live degradation: the device/TPU hash path died mid-
                # stream. Fall back to the host hashlib pass for this
                # window AND the stream remainder -- bit-identical by
                # construction (same piece boundaries, same SHA-256).
                self._fell_back = True
                reason = (
                    "failpoint"
                    if isinstance(e, failpoints.FailpointError)
                    else "device_error"
                )
                from kraken_tpu.utils.metrics import REGISTRY

                REGISTRY.counter(
                    "ingest_fallbacks_total",
                    "Ingest windows rerouted to the host hash path after"
                    " a device-path fault (one increment per fallback"
                    " event, not per rerouted window)",
                ).inc(reason=reason)
                _log.warning(
                    "ingest window hash failed on %s (%s); host hash "
                    "path takes the stream remainder",
                    self.pipeline.hasher.name, e,
                )
                return self._host_window(view, plen)
        finally:
            lease.release()
            self._sem.release()

    def _hasher_window(self, view, plen: int) -> np.ndarray:
        """The configured hasher's path for one window (device staged,
        or the hasher's own batch call)."""
        m, ragged = divmod(len(view), plen)
        hasher = self.pipeline.hasher
        if m > 0 and ragged == 0 and hasattr(hasher, "stage_window"):
            arr = np.frombuffer(view, dtype=np.uint8).reshape(m, plen)
            if failpoints.fire("ingest.window.transfer"):
                raise failpoints.FailpointError("ingest.window.transfer")
            with timed_stage("transfer", self._bill):
                handle = hasher.stage_window(arr, plen)
            if failpoints.fire("ingest.window.hash"):
                raise failpoints.FailpointError("ingest.window.hash")
            with timed_stage("hash", self._bill):
                return hasher.hash_staged_window(handle)
        # CPU HashPool path, ragged final window, hashers without the
        # staged protocol: one batch call, billed to hash. Bit-identical
        # by definition -- same boundaries.
        if failpoints.fire("ingest.window.hash"):
            raise failpoints.FailpointError("ingest.window.hash")
        with timed_stage("hash", self._bill):
            return hasher.hash_pieces(view, plen)

    def _host_window(self, view, plen: int) -> np.ndarray:
        """Inline hashlib piece pass -- the degradation target. No
        device, no pool, no shared state: cannot fail the way the
        primary path just did."""
        import hashlib

        nbytes = len(view)
        n = max(1, -(-nbytes // plen)) if nbytes else 0
        out = np.empty((n, DIGEST_SIZE), dtype=np.uint8)
        with timed_stage("hash", self._bill):
            for i in range(n):
                piece = view[i * plen:(i + 1) * plen]
                out[i] = np.frombuffer(
                    hashlib.sha256(piece).digest(), dtype=np.uint8
                )
        return out
