"""Component assembly: wire stores, schedulers, and HTTP servers into
runnable origin / tracker / agent nodes.

Mirrors the reference's per-binary ``cmd`` wiring (uber/kraken agent/cmd,
origin/cmd, tracker/cmd -- upstream paths, unverified; SURVEY.md SS2.4/SS3.3)
as in-process node objects: the CLI runs one per process; the herd tests
run several per process.

Config keys follow the component YAML shape (SURVEY.md SS5 config):
``hasher: tpu|cpu`` selects the piece-hash plane, exactly as the north
star specifies.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import os
from typing import Optional

from aiohttp import web

from kraken_tpu.backend import Manager as BackendManager
from kraken_tpu.agent.server import AgentServer
from kraken_tpu.core.digest import Digest, DigestError
from kraken_tpu.core.hasher import get_hasher
from kraken_tpu.core.ingest import IngestConfig, IngestPipeline
from kraken_tpu.core.peer import PeerIDFactory
from kraken_tpu.origin.blobrefresh import Refresher
from kraken_tpu.origin.client import ClusterClient
from kraken_tpu.origin.metainfogen import Generator, PieceLengthConfig
from kraken_tpu.origin.server import OriginServer, QuorumConfig
from kraken_tpu.origin.writeback import WritebackExecutor
from kraken_tpu.persistedretry import Manager as RetryManager, TaskStore
from kraken_tpu.placement import Ring
from kraken_tpu.placement.healthcheck import ActiveMonitor
from kraken_tpu.utils import failpoints
from kraken_tpu.utils.bandwidth import BandwidthLimiter
from kraken_tpu.utils.deadline import RPCConfig
from kraken_tpu.utils.httputil import HTTPClient, base_url
from kraken_tpu.utils.metrics import REGISTRY, FailureMeter, instrument_app
from kraken_tpu.utils.profiler import (
    PROFILER,
    LoopLagMonitor,
    ProfilerConfig,
)
from kraken_tpu.utils.canary import CanaryConfig, CanaryProber
from kraken_tpu.utils.resources import ResourceSentinel, ResourcesConfig
from kraken_tpu.utils.slo import SLO, SLOConfig
from kraken_tpu.utils.trace import TRACER, TraceConfig
from kraken_tpu.p2p.delta import DeltaConfig, DeltaPlanner
from kraken_tpu.p2p.pex import PexConfig
from kraken_tpu.p2p.scheduler import Scheduler, SchedulerConfig
from kraken_tpu.p2p.storage import (
    AgentTorrentArchive,
    BatchedVerifier,
    OriginTorrentArchive,
)
from kraken_tpu.store import CAStore
from kraken_tpu.store.chunkstore import ChunkGC, ChunkStore, ChunkStoreConfig
from kraken_tpu.store.cleanup import CleanupConfig, CleanupManager
from kraken_tpu.store.recovery import run_fsck, write_clean_shutdown
from kraken_tpu.store.scrub import ScrubConfig, Scrubber
from kraken_tpu.tracker.client import (
    TrackerClient,  # noqa: F401 (re-exported; harnesses construct it)
    make_tracker_client,
    parse_tracker_addrs,
)
from kraken_tpu.tracker.peerstore import InMemoryPeerStore, RedisPeerStore
from kraken_tpu.tracker.server import TrackerServer

_log = logging.getLogger("kraken.assembly")

_ring_refresh_failures = FailureMeter(
    "ring_refresh_failures_total",
    "Origin-ring membership refreshes that raised (retried next interval)",
    _log,
)
_health_probe_failures = FailureMeter(
    "health_probe_failures_total",
    "Health-probe loop iterations that raised (retried next interval)",
    _log,
)


async def _cleanup_loop(manager: CleanupManager) -> None:
    """Periodic eviction sweep for a node's CAStore."""
    while True:
        await asyncio.sleep(manager.config.interval_seconds)
        try:
            evicted = await asyncio.to_thread(manager.run_once)
            if evicted:
                _log.info(
                    "evicted blobs",
                    extra={"count": len(evicted),
                           "store": manager.store.root},
                )
        except Exception:
            _log.exception("cleanup sweep failed")


async def _ring_refresh_loop(get_cluster, interval: float) -> None:
    """Periodic membership re-resolve for a node's origin cluster. The
    passive health filter only takes effect when the ring re-resolves, so
    every long-running holder of a ClusterClient needs this loop -- a dead
    origin otherwise stays in the replica lists forever. ``get_cluster``
    is a callable: herd harnesses attach the cluster after start."""
    while True:
        await asyncio.sleep(interval)
        cluster = get_cluster()
        try:
            if cluster is not None:
                await cluster.ring.refresh_async()
                # Same tick: drop passive-health verdicts for hosts that
                # left the hostlist -- the failure map must not grow
                # without bound under membership churn, and a departed
                # host's stale verdict must not greet a reused address.
                health = getattr(cluster, "health", None)
                if health is not None:
                    health.prune(cluster.ring.resolved_hosts)
        except Exception as e:
            # Flapping DNS / dead origins must show on /metrics, not
            # vanish into the retry loop.
            _ring_refresh_failures.record("ring refresh", e)


def _reload_tracker_addrs(node, spec) -> None:
    """SIGHUP ``tracker:`` handling shared by agent and origin: a fleet
    client swaps its membership live (ownership re-shards, ~1/N of
    swarms move); a single-host client retargets when the new list is
    still one addr. Growing 1 -> N needs a restart -- the client
    protocol object is chosen at construction."""
    client = node._tracker_client
    if client is None or spec is None:
        return
    addrs = parse_tracker_addrs(spec)
    if not addrs:
        return
    node.tracker_addr = ",".join(addrs)
    if hasattr(client, "set_addrs"):
        client.set_addrs(addrs)
        _log.info("tracker fleet addrs reloaded", extra={"addrs": addrs})
    elif len(addrs) == 1:
        client.addr = addrs[0]
        _log.info("tracker addr reloaded", extra={"addr": addrs[0]})
    else:
        _log.warning(
            "tracker list grew from one addr to %d: the single->fleet"
            " topology change requires a restart", len(addrs),
        )


def _rpc_config(rpc) -> RPCConfig:
    """Normalize the YAML ``rpc:`` section (dict) / an RPCConfig / None
    into one RPCConfig -- every node carries the same knob shape."""
    if isinstance(rpc, RPCConfig):
        return rpc
    return RPCConfig.from_dict(rpc)


def _resources_config(resources) -> ResourcesConfig:
    """Same normalization for the YAML ``resources:`` section."""
    if isinstance(resources, ResourcesConfig):
        return resources
    return ResourcesConfig.from_dict(resources)


def _trace_config(trace_cfg) -> TraceConfig:
    """Same normalization for the YAML ``trace:`` section."""
    if isinstance(trace_cfg, TraceConfig):
        return trace_cfg
    return TraceConfig.from_dict(trace_cfg)


def _delta_config(delta) -> DeltaConfig:
    """Same normalization for the YAML ``delta:`` section."""
    if isinstance(delta, DeltaConfig):
        return delta
    return DeltaConfig.from_dict(delta)


def _pex_config(pex) -> PexConfig:
    """Same normalization for the YAML ``pex:`` section."""
    if isinstance(pex, PexConfig):
        return pex
    return PexConfig.from_dict(pex)


def _profiling_config(profiling) -> ProfilerConfig:
    """Same normalization for the YAML ``profiling:`` section."""
    if isinstance(profiling, ProfilerConfig):
        return profiling
    return ProfilerConfig.from_dict(profiling)


def _chunkstore_config(chunkstore) -> ChunkStoreConfig:
    """Same normalization for the YAML ``chunkstore:`` section."""
    if isinstance(chunkstore, ChunkStoreConfig):
        return chunkstore
    return ChunkStoreConfig.from_dict(chunkstore)


def _slo_config(slo) -> SLOConfig:
    """Same normalization for the YAML ``slo:`` section."""
    if isinstance(slo, SLOConfig):
        return slo
    return SLOConfig.from_dict(slo)


def _ingest_config(ingest) -> IngestConfig:
    """Same normalization for the YAML ``ingest:`` section."""
    if isinstance(ingest, IngestConfig):
        return ingest
    return IngestConfig.from_dict(ingest)


def _quorum_config(quorum) -> QuorumConfig:
    """Same normalization for the YAML ``quorum:`` section."""
    if isinstance(quorum, QuorumConfig):
        return quorum
    return QuorumConfig.from_dict(quorum)


def _sync_ingest(node) -> None:
    """Attach or retune the pipelined ingest plane from
    ``node.ingest_config``. First call with a config builds the pipeline
    and threads it through the generator and (if started) the blobserver
    -- so enabling ingest on a running origin is a SIGHUP, not a restart.
    Subsequent calls live-apply knob changes; disabling requires a
    restart (in-flight sessions would dangle)."""
    if node.ingest_config is None:
        return
    if node.ingest_pipeline is None:
        node.ingest_pipeline = IngestPipeline(
            node.generator.hasher, node.ingest_config
        )
        node.generator.pipeline = node.ingest_pipeline
        if node.server is not None:
            node.server._ingest_pipeline = node.ingest_pipeline
            # Stream-time piece hashing turns on with the pipeline even
            # on device-hasher origins; the pipeline schedules its own
            # workers, so the legacy stream pool steps aside.
            if node.server._stream_piece_length == 0:
                node.server._stream_piece_length = (
                    node.generator.piece_lengths.piece_length(0)
                )
            node.server._stream_hash_pool = None
    else:
        node.ingest_pipeline.apply(node.ingest_config)
    if node.server is not None:
        # Robustness knobs ride the same SIGHUP: resume journaling and
        # serve-while-ingest flip live (they gate per-request behavior,
        # no rebuild needed).
        node.server.resume_enabled = node.ingest_config.resume
        node.server.serve_while_ingest = node.ingest_config.serve_while_ingest


def _canary_config(canary) -> CanaryConfig:
    """Same normalization for the YAML ``canary:`` section."""
    if isinstance(canary, CanaryConfig):
        return canary
    return CanaryConfig.from_dict(canary)


def _apply_slo(component: str, cfg: SLOConfig) -> None:
    """Apply a node's ``slo:`` section to the process-global SLO
    manager (utils/slo.py SLO -- one per process, like the TRACER;
    in-process herds share it and the last-started node wins).  The
    evaluator thread follows the enabled flag."""
    SLO.node = component
    SLO.apply(cfg)


def _sync_chunkstore(node) -> None:
    """Attach (or re-configure) a node's chunk tier to match its
    ``chunkstore:`` config -- at construction AND on SIGHUP reload.
    The tier object attaches when the knob is on OR when the tier
    directory already holds state: a node restarted with the knob
    turned off must keep serving its manifest-backed blobs (disabling
    gates NEW conversions only; the runbook's rollback path is
    materialize-or-repull, docs/OPERATIONS.md "Chunk store")."""
    store: CAStore = node.store
    cfg: ChunkStoreConfig = node.chunkstore_config
    if store.chunkstore is not None:
        store.chunkstore.config = cfg
        return
    chunks_root = os.path.join(store.root, "chunks")
    if cfg.enabled or os.path.isdir(chunks_root):
        store.attach_chunkstore(ChunkStore(
            chunks_root, cfg,
            quarantine_dir=store.quarantine_dir,
            durability=store.durability,
        ))


def _sync_chunk_gc(node) -> None:
    """Start the budgeted zero-ref reaper once a tier is attached and a
    loop is running (start() and the live-enable reload path)."""
    if node.store.chunkstore is None or node.chunk_gc is not None:
        return
    try:
        asyncio.get_running_loop()
    except RuntimeError:
        return  # offline reload: the next start() picks it up
    node.chunk_gc = ChunkGC(node.store.chunkstore)
    node.chunk_gc.start()


def _apply_profiling(component: str, cfg: ProfilerConfig,
                     store_root: str = "") -> ProfilerConfig:
    """Apply a node's ``profiling:`` section to the process-global
    sampler (utils/profiler.py PROFILER -- one per process, like the
    TRACER; in-process herds share it and the last-started node wins).
    An empty ``dump_dir`` defaults beside the trace dumps under the
    node's store root, so a degradation postmortem's spans and stacks
    land in one directory; store-less nodes (tracker) skip file
    captures unless a dir is configured explicitly. Also registers the
    tracer's dump-trigger hook: every flight-recorder trigger (breaker
    trip, DeadlineExceeded, resource breach, lameduck) now captures a
    profile window too."""
    if not cfg.dump_dir and store_root:
        cfg = dataclasses.replace(
            cfg, dump_dir=os.path.join(store_root, "traces")
        )
    PROFILER.node = component
    PROFILER.apply(cfg)
    TRACER.on_trigger = PROFILER.trigger_capture
    return cfg


def _apply_trace(component: str, cfg: TraceConfig,
                 store_root: str = "") -> None:
    """Apply a node's ``trace:`` section to the process-global tracer
    (utils/trace.py TRACER -- one per process, like the metric
    REGISTRY; in-process herd tests share it and the last-started node
    wins, exactly as with the registry). An empty ``dump_dir`` defaults
    under the node's store root so flight-recorder postmortems land
    next to the data they describe; store-less nodes (tracker) skip
    file dumps unless a dir is configured explicitly."""
    if not cfg.dump_dir and store_root:
        cfg = dataclasses.replace(
            cfg, dump_dir=os.path.join(store_root, "traces")
        )
    TRACER.apply(cfg)
    TRACER.node = component


def _sync_loop_monitor(node, component: str) -> None:
    """Bring a node's LoopLagMonitor in line with its profiling config
    -- used at start AND on SIGHUP reload, so enabling profiling live
    really starts the heartbeat and disabling really stops it (knob
    changes apply in place). Keeps the sentinel's loop_lag probe
    pointed at the live monitor (or None), so the ``loop_lag`` budget
    follows the toggle too."""
    cfg = node.profiling_config
    sentinel = getattr(node, "sentinel", None)
    if cfg.enabled and node.loop_monitor is None:
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            return  # no loop (offline reload): nothing to heartbeat yet
        node.loop_monitor = LoopLagMonitor(component, cfg)
        node.loop_monitor.start()
    elif not cfg.enabled and node.loop_monitor is not None:
        node.loop_monitor.stop()
        node.loop_monitor = None
    elif node.loop_monitor is not None:
        node.loop_monitor.apply(cfg)
    if sentinel is not None:
        sentinel.loop_lag_probe = (
            node.loop_monitor.p99 if node.loop_monitor is not None else None
        )


def _start_sentinel(node, component: str) -> ResourceSentinel:
    """Build, register, and start a node's resource sentinel. The
    sustained-breach hook enters lameduck (idempotent, non-blocking):
    /health flips to 503, the deploy system observes and SIGTERMs for
    the full drain+stop -- the same operator contract as
    POST /debug/lameduck."""

    def shed(kinds: list[str]) -> None:
        REGISTRY.counter(
            "resource_breach_drains_total",
            "Lameduck drains entered by the resource sentinel",
        ).inc(component=component)
        if node.server is not None:
            node.server.enter_lameduck()
        elif node.scheduler is not None:
            node.scheduler.enter_lameduck()

    monitor = getattr(node, "loop_monitor", None)
    sentinel = ResourceSentinel(
        component,
        node.resources_config,
        scheduler=node.scheduler,
        store=node.store,
        upload_ttl_seconds=(
            node.cleanup.config.upload_ttl_seconds
            if node.cleanup is not None else 6 * 3600
        ),
        on_sustained_breach=shed,
        # The loop-lag monitor's recent p99 feeds the sentinel's
        # `loop_lag` budget kind (resources: loop_lag_p99_seconds) --
        # a wedged event loop drains like any other resource breach.
        loop_lag_probe=monitor.p99 if monitor is not None else None,
        # The persistedretry Manager's per-kind pending counts feed the
        # `retry_queue_depth` gauge and the `retry_queue` budget kind --
        # a wedged replication/hint queue pages before it silently grows
        # unbounded.
        retry_probe=(
            node.retry.queue_depths
            if getattr(node, "retry", None) is not None else None
        ),
    )
    sentinel.start()
    return sentinel


async def _drain_node(server, scheduler, timeout: float,
                      component: str) -> None:
    """Shared lameduck drain: enter drain mode, then wait (up to
    ``timeout``) for in-flight work to finish -- established p2p conns
    completing and churning out, streaming HTTP bodies landing. The
    caller runs the normal stop() afterwards; by then the hard teardown
    cancels nothing that mattered."""
    # SIGTERM/operator drain is a degradation event (the clean stop()
    # path is not): persist the flight recorder before the conns drain
    # away -- the spans of whatever prompted the drain are in the ring.
    TRACER.trigger_dump("lameduck", f"{component}: drain entered")
    if server is not None:
        server.enter_lameduck()
    elif scheduler is not None:
        scheduler.enter_lameduck()
    REGISTRY.gauge(
        "lameduck", "1 while this node is draining (SIGTERM/debug entry)"
    ).set(1, component=component)
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        conns = scheduler.num_active_conns if scheduler is not None else 0
        inflight = server.inflight_work if server is not None else 0
        if conns == 0 and inflight == 0:
            _log.info(
                "drain quiesced", extra={"component": component}
            )
            return
        await asyncio.sleep(0.05)
    _log.warning(
        "drain timeout: proceeding to hard stop",
        extra={
            "component": component,
            "active_conns": scheduler.num_active_conns if scheduler else 0,
            "inflight": server.inflight_work if server else 0,
        },
    )


async def _serve(app: web.Application, host: str, port: int,
                 component: str = "", ssl_context=None):
    # Chaos guard: refuse to bind a listener while failpoints are armed
    # without the explicit acknowledgement (utils/failpoints.py) -- a
    # stray `failpoints:` config section or a leftover test arm() must
    # fail the boot loudly, never inject silently in rotation.
    failpoints.FAILPOINTS.assert_safe(component or "node")
    if component:
        # Per-endpoint latency/status metrics + GET /metrics on every
        # component app (lib/middleware + tally in the reference --
        # upstream path, unverified; SURVEY.md SS2.4/SS5).
        instrument_app(app, component)
    # handler_cancellation: aiohttp >= 3.8 stopped cancelling handlers on
    # client disconnect by default; this codebase is written for the
    # cancelling contract (the 499 accounting in instrument_app, the
    # upload-tracker invalidation on aborted PATCH bodies, the shielded
    # jax-profile stop) -- without it a disconnected client leaves its
    # handler running to completion, e.g. a 30 s profile capture pinning
    # the process-global profiler after the caller gave up.
    runner = web.AppRunner(app, handler_cancellation=True)
    await runner.setup()
    site = web.TCPSite(runner, host, port, ssl_context=ssl_context)
    await site.start()
    actual = site._server.sockets[0].getsockname()[1]
    return runner, actual


class TrackerNode:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 origin_cluster: ClusterClient | None = None,
                 announce_interval_seconds: float = 3.0,
                 peer_ttl_seconds: float = 30.0,
                 ring_refresh_seconds: float = 5.0,
                 redis_addr: str = "",
                 fleet: str | list[str] | None = None,
                 self_addr: str = "",
                 ssl_context=None,
                 rpc: dict | RPCConfig | None = None,
                 trace: dict | TraceConfig | None = None,
                 profiling: dict | ProfilerConfig | None = None,
                 slo: dict | SLOConfig | None = None):
        self.host = host
        self.port = port
        self.rpc = _rpc_config(rpc)
        # Tracker HA fleet (docs/OPERATIONS.md "Tracker fleet"): the
        # full fleet's addrs + this tracker's own addr as it appears
        # there. Drives shard ownership and non-owner announce
        # forwarding; clients shard/fail over on their own copy of the
        # same list. SIGHUP live-reloads (`fleet:` / `self_addr:`).
        self.fleet_addrs = parse_tracker_addrs(fleet or [])
        self.self_addr = self_addr
        # Store-less node: dump_dir stays "" (no file postmortems)
        # unless the YAML sets one explicitly; /debug/trace still works.
        self.trace_config = _trace_config(trace)
        # Same for profile captures: the sampler + loop-lag monitor run
        # regardless (the /debug/pprof surfaces answer live).
        self.profiling_config = _profiling_config(profiling)
        # SLO plane (utils/slo.py): burn-rate evaluation + /debug/slo.
        # A tracker records no SLIs itself, but the surface still
        # answers (empty burn) so `kraken-tpu status` needs no special
        # case. YAML `slo:`; SIGHUP live-reloads.
        self.slo_config = _slo_config(slo)
        self.loop_monitor: Optional[LoopLagMonitor] = None
        # Redis-protocol store: swarm survives tracker restarts and can be
        # shared by several trackers; default in-memory store re-heals via
        # TTL instead.
        peer_store = (
            RedisPeerStore(redis_addr, ttl_seconds=peer_ttl_seconds)
            if redis_addr
            else InMemoryPeerStore(ttl_seconds=peer_ttl_seconds)
        )
        self.server = TrackerServer(
            peer_store=peer_store,
            origin_cluster=origin_cluster,
            announce_interval_seconds=announce_interval_seconds,
            fleet_addrs=self.fleet_addrs,
            self_addr=self.self_addr,
            # Trackers sharing a Redis store already rendezvous there:
            # non-owner forwarding would only duplicate writes.
            shared_store=bool(redis_addr),
        )
        self.ring_refresh = ring_refresh_seconds
        self.ssl_context = ssl_context
        self._runner: Optional[web.AppRunner] = None
        self._refresh_task: Optional[asyncio.Task] = None

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"

    async def start(self) -> None:
        _apply_trace("tracker", self.trace_config)
        self.profiling_config = _apply_profiling(
            "tracker", self.profiling_config
        )
        _apply_slo("tracker", self.slo_config)
        _sync_loop_monitor(self, "tracker")
        self._runner, self.port = await _serve(
            self.server.make_app(), self.host, self.port, "tracker",
            ssl_context=self.ssl_context,
        )
        self._refresh_task = asyncio.create_task(_ring_refresh_loop(
            lambda: self.server.origin_cluster, self.ring_refresh
        ))

    def reload(self, cfg: dict) -> None:
        """SIGHUP: apply the ``trace:``, ``fleet:``/``self_addr:``, and
        ``rpc:`` sections live (the latter to the metainfo-proxy cluster
        client -- hedge delay, read deadline, brown-out threshold on its
        breaker)."""
        # Fleet membership swap: ownership re-shards on the next
        # announce (add/remove moves ~1/N of the swarms -- the
        # rendezvous-hash property the rebalance test pins). An EMPTY
        # parse is skipped, not applied: the shipped base.yaml carries
        # `fleet: ""`, and a SIGHUP for an unrelated section must not
        # silently dissolve a fleet configured via --fleet flags
        # (topology changes need a restart, like the client side).
        reload_fleet = parse_tracker_addrs(cfg.get("fleet") or [])
        if reload_fleet:
            self.fleet_addrs = reload_fleet
            if cfg.get("self_addr"):
                self.self_addr = cfg["self_addr"].strip()
            self.server.set_fleet(self.fleet_addrs, self.self_addr)
            _log.info(
                "tracker fleet reloaded",
                extra={"fleet": self.fleet_addrs, "self": self.self_addr},
            )
        if cfg.get("trace") is not None:
            self.trace_config = _trace_config(cfg["trace"])
            _apply_trace("tracker", self.trace_config)
        if cfg.get("profiling") is not None:
            self.profiling_config = _apply_profiling(
                "tracker", _profiling_config(cfg["profiling"])
            )
            _sync_loop_monitor(self, "tracker")
        if cfg.get("slo") is not None:
            self.slo_config = _slo_config(cfg["slo"])
            _apply_slo("tracker", self.slo_config)
        if cfg.get("rpc") is None:
            return
        self.rpc = _rpc_config(cfg["rpc"])
        c = self.server.origin_cluster
        if c is not None:
            c.hedge_delay = self.rpc.hedge_delay_seconds or None
            c.deadline_seconds = self.rpc.request_deadline_seconds
            if c.health is not None and hasattr(c.health, "brownout_threshold"):
                c.health.brownout_threshold = (
                    self.rpc.brownout_threshold_seconds
                )
        _log.info("rpc config reloaded", extra={"node": self.addr})

    async def drain(self, timeout: float | None = None) -> None:
        """Lameduck drain (SIGTERM / POST /debug/lameduck): /health
        flips to 503 and new announces/proxy reads are refused -- fleet
        clients fail over to the next ring tracker immediately, which is
        what makes a rolling tracker restart routine. In-flight handlers
        finish up to ``drain_timeout``; :meth:`stop` follows."""
        await _drain_node(
            self.server, None,
            self.rpc.drain_timeout_seconds if timeout is None else timeout,
            "tracker",
        )

    async def stop(self) -> None:
        # Refusal-before-teardown, as on agent/origin: no new announce
        # lands while the runner below is mid-teardown.
        self.server.enter_lameduck()
        if self._refresh_task:
            self._refresh_task.cancel()
        if self.loop_monitor:
            self.loop_monitor.stop()
        if self._runner:
            await self._runner.cleanup()
        await self.server.close()


class OriginNode:
    """Origin: CAStore + TPU metainfo-gen + blobserver + P2P seeding."""

    def __init__(
        self,
        store_root: str,
        tracker_addr: str = "",
        host: str = "127.0.0.1",
        http_port: int = 0,
        p2p_port: int = 0,
        hasher: str = "cpu",
        hash_workers: int = 1,
        backends: BackendManager | None = None,
        ring: Ring | None = None,
        self_addr: str = "",
        retry_db: str = "",
        piece_lengths: PieceLengthConfig | None = None,
        cleanup: CleanupConfig | None = None,
        dedup: bool = True,
        dedup_index: str = "dict",  # "compact" for million-blob corpora
        dedup_budget_bytes: int | None = None,
        dedup_low_j_bands: int | None = None,  # None = default tier; 0 = off
        hash_window_bytes: int = 256 * 1024 * 1024,
        health_interval_seconds: float = 5.0,
        health_fail_threshold: int = 3,
        scheduler_config_doc: dict | None = None,
        p2p_bandwidth: dict | None = None,
        ssl_context=None,
        durability: str = "rename",
        scrub: dict | ScrubConfig | None = None,
        fsck: bool = True,
        task_timeout_seconds: float = 1800.0,
        rpc: dict | RPCConfig | None = None,
        resources: dict | ResourcesConfig | None = None,
        trace: dict | TraceConfig | None = None,
        delta: dict | DeltaConfig | None = None,
        profiling: dict | ProfilerConfig | None = None,
        chunkstore: dict | ChunkStoreConfig | None = None,
        slo: dict | SLOConfig | None = None,
        ingest: dict | IngestConfig | None = None,
        quorum: dict | QuorumConfig | None = None,
    ):
        from kraken_tpu.origin.dedup import DedupIndex

        self.host = host
        self.http_port = http_port
        self.p2p_port = p2p_port
        self.tracker_addr = tracker_addr
        self.store = CAStore(store_root, durability=durability)
        # Content-addressed chunk tier (store/chunkstore.py): keep each
        # chunk once, serve blobs as manifests. YAML `chunkstore:`;
        # shipped OFF; SIGHUP live-reloads (enable = attach + convert
        # from the next dedup pass on). Attached BEFORE fsck so the
        # startup pass covers the tier.
        self.chunkstore_config = _chunkstore_config(chunkstore)
        self.chunk_gc: Optional[ChunkGC] = None
        _sync_chunkstore(self)
        self.hasher_name = hasher
        # hash_workers sizes the HOST piece-hash pool (cpu hasher only;
        # device hashers parallelize over the batch axis instead). 1 =
        # one pool worker -- piece hashing already overlaps the serial
        # blob digest at stream time; raise toward the core count on
        # multi-core origins (docs/OPERATIONS.md). 0 = strictly serial.
        self.hash_workers = hash_workers
        hasher_obj = get_hasher(hasher, workers=hash_workers)
        # Pipelined ingest plane (core/ingest.py): YAML `ingest:` turns
        # the upload spool -> piece-hash path into an overlapped window
        # stream (read || pack || transfer || hash). None = the serial
        # legacy path. SIGHUP live-reloads knobs (and live-ENABLES the
        # plane on a running origin).
        self.ingest_config = None if ingest is None else _ingest_config(ingest)
        self.ingest_pipeline = (
            IngestPipeline(hasher_obj, self.ingest_config)
            if self.ingest_config is not None
            else None
        )
        self.generator = Generator(
            self.store,
            hasher=hasher_obj,
            piece_lengths=piece_lengths,
            window_bytes=hash_window_bytes,
            pipeline=self.ingest_pipeline,
        )
        self.dedup = (
            DedupIndex(
                self.store, hasher=get_hasher(hasher, workers=hash_workers),
                index_kind=dedup_index,
                index_budget_bytes=dedup_budget_bytes,
                low_j_bands=dedup_low_j_bands,
            )
            if dedup else None
        )
        self.backends = backends
        self.refresher = (
            Refresher(self.store, backends, self.generator) if backends else None
        )
        # task_timeout_seconds bounds ONE executor run (a hung writeback
        # socket must not stall every task kind); a cut task reschedules
        # with backoff. Size it above your slowest legitimate transfer
        # (multi-GiB writeback over a slow link); 0 disables.
        self.retry = (
            RetryManager(
                TaskStore(retry_db or f"{store_root}/retry.db"),
                task_timeout_seconds=task_timeout_seconds,
            )
        )
        self.writeback = (
            WritebackExecutor(self.store, backends, self.retry) if backends else None
        )
        self.ring = ring
        self.self_addr = self_addr
        self.cleanup = (
            CleanupManager(
                self.store, cleanup,
                on_evict=self.dedup.remove_sync if self.dedup else None,
                after_evict=self._after_evict,
            )
            if cleanup
            else None
        )
        self.health_interval = health_interval_seconds
        self.health_fail_threshold = health_fail_threshold
        self._scheduler_doc = scheduler_config_doc
        # YAML p2p_bandwidth: {egress_bps, ingress_bps[, burst]} -- one
        # limiter shared by every conn shapes this HOST's piece traffic
        # (the reference caps per-host agent bandwidth the same way).
        self.p2p_bandwidth = (
            BandwidthLimiter(**p2p_bandwidth) if p2p_bandwidth else None
        )
        self.ssl_context = ssl_context
        # Self-healing storage plane (store/recovery.py, store/scrub.py):
        # fsck reconciles the tree before any listener binds; the
        # scrubber re-verifies at-rest bytes on a budgeted cycle and
        # feeds corruption into the heal plane (origin/server.py).
        self.fsck_enabled = fsck
        self.scrub_config = (
            ScrubConfig(**scrub) if isinstance(scrub, dict) else scrub
        )
        # Overload & degradation knobs (YAML `rpc:` -- deadlines, hedge
        # delay, brown-out threshold, drain timeout; live-reloadable).
        self.rpc = _rpc_config(rpc)
        # Resource sentinel (utils/resources.py): periodic fd/RSS/task/
        # bufpool/conn/orphan audit with YAML budgets (`resources:`);
        # a sustained breach can opt into the lameduck drain.
        self.resources_config = _resources_config(resources)
        # Distributed tracing + flight recorder (utils/trace.py): YAML
        # `trace:` knobs -- sampling, slow-tail threshold, ring size,
        # dump throttle; SIGHUP live-reloads. Applied at start().
        self.trace_config = _trace_config(trace)
        # Delta-transfer plane (p2p/delta.py): origin side serves chunk
        # recipes on GET .../recipe when enabled (shipped OFF). YAML
        # `delta:`; SIGHUP live-reloads.
        self.delta_config = _delta_config(delta)
        # Continuous profiling plane (utils/profiler.py): sampler hz,
        # loop-lag knobs, capture throttle. YAML `profiling:`; SIGHUP
        # live-reloads. Applied at start() (before the scheduler forks
        # seed-serve workers, which inherit the applied config).
        self.profiling_config = _profiling_config(profiling)
        # SLO plane (utils/slo.py): upload/heal/replication SLIs feed
        # the burn-rate evaluators; /debug/slo on the mux. YAML `slo:`;
        # SIGHUP live-reloads.
        self.slo_config = _slo_config(slo)
        # Quorum write plane (origin/server.py QuorumConfig): commit
        # acks wait for write_quorum replicas, unreachable replicas get
        # hinted handoff. YAML `quorum:`; shipped write_quorum: 1 (the
        # compatible single-copy ack); SIGHUP live-reloads.
        self.quorum_config = _quorum_config(quorum)
        self.loop_monitor: Optional[LoopLagMonitor] = None
        self.sentinel: Optional[ResourceSentinel] = None
        self.scrubber: Optional[Scrubber] = None
        self.fsck_report = None
        self.monitor: Optional[ActiveMonitor] = None
        self.scheduler: Optional[Scheduler] = None
        self.server: Optional[OriginServer] = None
        self._runner: Optional[web.AppRunner] = None
        self._tracker_client: Optional[TrackerClient] = None
        self._health_http: Optional[HTTPClient] = None
        self._health_task: Optional[asyncio.Task] = None
        self._cleanup_task: Optional[asyncio.Task] = None
        self._reseed_task: Optional[asyncio.Task] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._repair_tasks: set[asyncio.Task] = set()

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.http_port}"

    def _after_evict(self, d: Digest) -> None:
        """Runs in the cleanup sweep's worker thread AFTER the bytes are
        gone: stop seeding (hop to the event loop -- scheduler state is
        loop-owned). Post-delete ordering matters: unseeding while the
        blob still existed would let an inbound handshake resurrect the
        control via the metainfo resolver."""
        loop, sched = self._loop, self.scheduler
        if loop is not None and sched is not None:
            loop.call_soon_threadsafe(sched.unseed, d)

    def _resolve_metainfo(self, name: str, namespace: str):
        try:
            return self.generator.get_cached(Digest.from_hex(name))
        except DigestError:
            return None

    def _on_scrub_corrupt(self, d: Digest, ns: str) -> None:
        """Scrub-task context (event loop), AFTER the blob moved to
        quarantine: every derived plane must drop it (the dedup index
        would hand out a ghost; the scheduler would advertise bytes we
        no longer hold), then the heal plane restores it."""
        if self.dedup is not None:
            try:
                # Sidecar already moved with the blob; remove_sync
                # adjusts the ledger from whatever is still readable.
                self.dedup.remove_sync(d)
            except Exception:
                _log.warning(
                    "dedup drop of quarantined blob failed",
                    extra={"digest": d.hex}, exc_info=True,
                )
        if self.scheduler is not None:
            self.scheduler.unseed(d)
        if self.server is not None:
            self.server.enqueue_heal(ns, d)

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        # Trace config FIRST: the scheduler start below forks seed-serve
        # workers, which inherit the tracer's applied config wholesale.
        _apply_trace("origin", self.trace_config, self.store.root)
        # Profiling config before the fork too (workers restart their
        # own sampler from the inherited config), and the loop-lag
        # heartbeat before the sentinel (which probes its p99).
        self.profiling_config = _apply_profiling(
            "origin", self.profiling_config, self.store.root
        )
        _apply_slo("origin", self.slo_config)
        _sync_loop_monitor(self, "origin")
        # Startup fsck BEFORE any listener binds: the tree must be
        # reconciled (orphans swept, crash-window blobs verified) before
        # the swarm, replication, or writeback can stream from it.
        if self.fsck_enabled:
            self.fsck_report = await asyncio.to_thread(
                run_fsck,
                self.store,
                upload_ttl_seconds=(
                    self.cleanup.config.upload_ttl_seconds
                    if self.cleanup
                    else 6 * 3600
                ),
                expect_namespace=True,
                # Journaled upload sessions are resumable crash state,
                # not debris -- unless resume is configured off.
                resume=(
                    self.ingest_config.resume
                    if self.ingest_config is not None
                    else True
                ),
            )
        # Fixed p2p port -> stable addr_hash identity across restarts (the
        # reference's default); ephemeral port -> random identity.
        factory = PeerIDFactory(
            PeerIDFactory.ADDR_HASH if self.p2p_port else PeerIDFactory.RANDOM
        )
        peer_id = factory.create(self.host, self.p2p_port)
        # The p2p scheduler seeds cached blobs; origins announce as origin
        # peers so trackers hand them out last. A comma-separated
        # tracker list builds the sharded fleet client (failover,
        # breakers, hedged metainfo reads -- tracker/client.py).
        self._tracker_client = make_tracker_client(
            self.tracker_addr, peer_id, self.host, 0, is_origin=True,
            announce_timeout_seconds=self.rpc.announce_timeout_seconds,
            request_deadline_seconds=self.rpc.request_deadline_seconds,
            hedge_delay_seconds=self.rpc.hedge_delay_seconds,
        )
        self.scheduler = Scheduler(
            peer_id=peer_id,
            ip=self.host,
            port=self.p2p_port,
            archive=OriginTorrentArchive(self.store, BatchedVerifier()),
            metainfo_client=self._tracker_client,
            announce_client=self._tracker_client,
            is_origin=True,
            metainfo_resolver=self._resolve_metainfo,
            config=self.build_scheduler_config(self._scheduler_doc),
            bandwidth=self.p2p_bandwidth,
        )
        await self.scheduler.start()
        self._tracker_client.port = self.scheduler.port
        self.server = OriginServer(
            store=self.store,
            generator=self.generator,
            refresher=self.refresher,
            writeback=self.writeback,
            retry=self.retry,
            ring=self.ring,
            self_addr=self.self_addr,
            scheduler=self.scheduler,
            dedup=self.dedup,
            cleanup=self.cleanup,
            # TPU origins piece-hash in one batched device pass at commit
            # (stream-time hashlib would bypass the device); CPU origins
            # piece-hash while the bytes stream in -- no re-read.
            stream_piece_hash=self.hasher_name == "cpu",
            rpc=self.rpc,
            delta=self.delta_config,
            ingest_pipeline=self.ingest_pipeline,
            ingest_resume=(
                self.ingest_config.resume
                if self.ingest_config is not None
                else True
            ),
            serve_while_ingest=(
                self.ingest_config.serve_while_ingest
                if self.ingest_config is not None
                else False
            ),
            quorum=self.quorum_config,
        )
        self._runner, self.http_port = await _serve(
            self.server.make_app(), self.host, self.http_port, "origin",
            ssl_context=self.ssl_context,
        )
        if not self.self_addr:
            self.self_addr = self.addr
            self.server.self_addr = self.addr
        self.retry.start()
        # Blobs fsck quarantined (crash-window corruption) enter the heal
        # plane now that the retry manager is polling: re-fetch from ring
        # replicas, backend read-through fallback (origin/server.py).
        if self.fsck_report is not None:
            from kraken_tpu.store.recovery import quarantine_namespace

            for hex_ in self.fsck_report.quarantined:
                self.server.enqueue_heal(
                    quarantine_namespace(self.store, hex_),
                    Digest.from_hex(hex_),
                )
        # Background integrity scrubber: budgeted re-verification of
        # at-rest bytes, corruption -> quarantine -> heal.
        if self.scrub_config is not None:
            self.scrubber = Scrubber(
                self.store,
                self.scrub_config,
                hasher=self.generator.hasher,
                on_corrupt=self._on_scrub_corrupt,
            )
            self.scrubber.start()
        # Resource sentinel: the in-process fd/RSS/task/orphan auditor
        # (utils/resources.py); budgets from the YAML `resources:`
        # section, surfaced on /debug/resources and /metrics.
        self.sentinel = _start_sentinel(self, "origin")
        # Chunk-tier GC: budgeted zero-ref chunk reaper (watermark
        # pressure bypasses the budget inside the cleanup sweep).
        _sync_chunk_gc(self)
        # Seed everything already on disk (origin startup behavior). A blob
        # whose metainfo sidecar was lost (partial disk restore, manual
        # cleanup) gets its metainfo REGENERATED -- otherwise it would stay
        # invisible to the swarm until explicitly touched. Regeneration
        # hashes the blob, so it runs as a background task, seeding each
        # blob as its metainfo lands.
        missing: list[Digest] = []
        for d in self.store.list_cache_digests():
            metainfo = self.generator.get_cached(d)
            if metainfo is not None:
                self.scheduler.seed(metainfo, "startup")
            else:
                missing.append(d)
        if missing:
            self._reseed_task = asyncio.create_task(self._reseed(missing))
        # Rebuild the dedup index from persisted sketch sidecars.
        if self.dedup is not None:
            await asyncio.to_thread(self.dedup.load_existing)
        # Eviction: periodic TTI + watermark sweeps (lib/store/cleanup.go
        # behavior -- upstream path, unverified; SURVEY.md SS2.3).
        if self.cleanup is not None:
            self._cleanup_task = asyncio.create_task(
                _cleanup_loop(self.cleanup)
            )
        # Failure plane (SURVEY.md SS5): probe ring peers, refresh
        # membership, and repair (re-replicate) on every change.
        if self.ring is not None:
            self._health_http = HTTPClient(timeout_seconds=2.0, retries=0)
            self.monitor = ActiveMonitor(
                probe=self._probe_origin,
                fail_threshold=self.health_fail_threshold,
            )
            if not self.ring.has_health_filter:
                self.ring.set_health_filter(self.monitor.filter)
            self.ring.on_change(self._on_ring_change)
            self._health_task = asyncio.create_task(self._health_loop())

    @staticmethod
    def build_scheduler_config(doc: dict | None) -> SchedulerConfig:
        """The origin's scheduler config: YAML ``scheduler:`` section over
        origin defaults. Origins serve swarms, so the per-torrent conn
        budget is far higher than agents' (a 10-conn cap on the sole
        initial seeder strangles flash crowds -- measured in bench_swarm).
        One source for boot AND reload: the same file must mean the same
        limits at both."""
        doc = dict(doc or {})
        conn = {
            "max_open_conns_per_torrent": 64,
            "max_global_conns": 4000,
            **(doc.pop("conn_state", None) or {}),
        }
        # Origins never download (they ARE the initial seed), so a
        # configured leech plane would only fork idle workers -- drop
        # the knobs even if a shared yaml sets them.
        doc.pop("leech_workers", None)
        doc.pop("leech_ring_mb", None)
        return SchedulerConfig.from_dict({**doc, "conn_state": conn})

    def reload(self, cfg: dict) -> None:
        """Apply a re-read config's ``scheduler:``, ``tracker:``, and
        ``rpc:`` sections live (SIGHUP)."""
        if self.scheduler is not None:
            self.scheduler.reload(
                self.build_scheduler_config(cfg.get("scheduler"))
            )
        _reload_tracker_addrs(self, cfg.get("tracker"))
        if cfg.get("rpc") is not None:
            self.apply_rpc(_rpc_config(cfg["rpc"]))
        if cfg.get("resources") is not None:
            self.resources_config = _resources_config(cfg["resources"])
            if self.sentinel is not None:
                self.sentinel.apply(self.resources_config)
        if cfg.get("trace") is not None:
            self.trace_config = _trace_config(cfg["trace"])
            _apply_trace("origin", self.trace_config, self.store.root)
        if cfg.get("delta") is not None:
            # Live enable/disable of the recipe endpoint: rollout step 1
            # (origins first) is a SIGHUP, not a restart.
            self.delta_config = _delta_config(cfg["delta"])
            if self.server is not None:
                self.server.delta_config = self.delta_config
        if cfg.get("profiling") is not None:
            self.profiling_config = _apply_profiling(
                "origin", _profiling_config(cfg["profiling"]),
                self.store.root,
            )
            _sync_loop_monitor(self, "origin")
        if cfg.get("chunkstore") is not None:
            # Live enable = rollout step (attach tier + start GC; new
            # blobs convert from the next dedup pass). Live disable
            # stops NEW conversions only -- manifest-backed blobs keep
            # serving.
            self.chunkstore_config = _chunkstore_config(cfg["chunkstore"])
            _sync_chunkstore(self)
            _sync_chunk_gc(self)
        if cfg.get("slo") is not None:
            self.slo_config = _slo_config(cfg["slo"])
            _apply_slo("origin", self.slo_config)
        if cfg.get("ingest") is not None:
            # Live knob retune -- and live ENABLE: an origin started
            # without `ingest:` grows the pipeline on SIGHUP (rollout
            # step; docs/OPERATIONS.md runbook). Disable needs a restart.
            self.ingest_config = _ingest_config(cfg["ingest"])
            _sync_ingest(self)
        if cfg.get("quorum") is not None:
            # Durability posture is a SIGHUP, not a restart: raising
            # write_quorum starts gating acks from the NEXT commit.
            self.quorum_config = _quorum_config(cfg["quorum"])
            if self.server is not None:
                self.server.quorum = self.quorum_config

    def apply_rpc(self, rpc: RPCConfig) -> None:
        """Swap the degradation knobs live: the announce budget, the
        drain timeout, and the heal cluster's hedge/deadline settings
        all take effect from the next call."""
        self.rpc = rpc
        if self._tracker_client is not None:
            self._tracker_client.announce_timeout = rpc.announce_timeout_seconds
            if hasattr(self._tracker_client, "request_deadline"):
                # Fleet client: the hedged-read knobs reload too.
                self._tracker_client.request_deadline = (
                    rpc.request_deadline_seconds
                )
                self._tracker_client.hedge_delay = (
                    rpc.hedge_delay_seconds or None
                )
        if self.server is not None:
            self.server.rpc = rpc
            c = self.server._heal_cluster
            if c is not None:
                c.hedge_delay = rpc.hedge_delay_seconds or None
                c.deadline_seconds = rpc.request_deadline_seconds
        _log.info("rpc config reloaded", extra={"node": self.self_addr})

    async def _reseed(self, missing: list[Digest]) -> None:
        """Regenerate lost metainfo sidecars and seed the blobs (runs in
        the background after startup; sequential so it never starves the
        serving path of hasher batches)."""
        for d in missing:
            try:
                # The motivating scenario -- a partial disk restore -- can
                # corrupt the blob along with losing its sidecar. Verify
                # the content hash BEFORE regenerating piece hashes from
                # it, or the swarm would happily serve wrong bytes as d
                # (agents verify pieces only against the regenerated
                # metainfo, never the whole-blob digest).
                if not await asyncio.to_thread(self._blob_matches, d):
                    _log.warning(
                        "reseed skipped: blob content does not match digest",
                        extra={"digest": d.hex},
                    )
                    continue
                if self.cleanup is not None:
                    self.cleanup.touch(d)  # a reseed backlog must not TTI-evict
                metainfo = await self.generator.generate(d)
                if not self.store.in_cache(d):
                    # Evicted mid-hash: drop the orphan sidecar generate()
                    # just rewrote and do not advertise a bodyless torrent.
                    from kraken_tpu.origin.metainfogen import (
                        TorrentMetaMetadata,
                    )

                    await asyncio.to_thread(
                        self.store.delete_metadata, d, TorrentMetaMetadata
                    )
                    continue
                self.scheduler.seed(metainfo, "startup")
            except Exception:
                _log.warning(
                    "startup reseed failed", extra={"digest": d.hex},
                    exc_info=True,
                )

    def _blob_matches(self, d: Digest) -> bool:
        import hashlib

        h = hashlib.sha256()
        with self.store.open_cache_file(d) as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        return h.hexdigest() == d.hex

    async def _probe_origin(self, host: str) -> bool:
        try:
            await self._health_http.get(
                f"{base_url(host)}/health", retry_5xx=False
            )
            return True
        except Exception:
            return False

    async def _health_loop(self) -> None:
        while True:
            await asyncio.sleep(self.health_interval)
            try:
                # One resolve per tick: probe last refresh's membership,
                # then refresh (which re-resolves off-loop -- DNS stalls
                # must not freeze the node -- and fires _on_ring_change on
                # membership change).
                peers = [
                    h for h in self.ring.resolved_hosts
                    if h != self.self_addr
                ]
                await self.monitor.check_all(peers)
                await self.ring.refresh_async()
                # Forget verdicts for hosts that left the membership --
                # the monitor map must not grow without bound under
                # churn, and a stale verdict must not greet a reused
                # address (placement/healthcheck.py prune).
                self.monitor.prune(self.ring.resolved_hosts)
            except Exception as e:
                _health_probe_failures.record("health probe sweep", e)

    def _on_ring_change(self, hosts: list[str]) -> None:
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return  # construction-time refresh: no loop, nothing to repair yet
        if self.server is None:
            return

        async def repair_and_log():
            n = await self.server.repair()
            _log.info(
                "ring changed; repair enqueued",
                extra={"node": self.self_addr, "members": hosts, "tasks": n},
            )

        t = loop.create_task(repair_and_log())
        self._repair_tasks.add(t)
        t.add_done_callback(self._repair_tasks.discard)

    async def drain(self, timeout: float | None = None) -> None:
        """Lameduck drain (SIGTERM path; docs/OPERATIONS.md runbook):
        stop announcing, fail /health so the ring routes away, refuse
        new uploads and p2p conns, and let in-flight pieces and upload
        bodies finish -- up to ``drain_timeout``. Call :meth:`stop`
        afterwards for the hard teardown."""
        await _drain_node(
            self.server, self.scheduler,
            self.rpc.drain_timeout_seconds if timeout is None else timeout,
            "origin",
        )

    async def stop(self) -> None:
        # Refusal-before-teardown, even on the non-drain path: entering
        # lameduck first means no NEW announce fires or conn lands in
        # the window where the teardown below is mid-flight.
        if self.server is not None:
            self.server.enter_lameduck()
        elif self.scheduler is not None:
            self.scheduler.enter_lameduck()
        if self._health_task:
            self._health_task.cancel()
        if self._cleanup_task:
            self._cleanup_task.cancel()
        if self._reseed_task:
            self._reseed_task.cancel()
        if self.sentinel:
            self.sentinel.stop()
        if self.loop_monitor:
            self.loop_monitor.stop()
        if self.scrubber:
            self.scrubber.stop()
        if self.chunk_gc:
            self.chunk_gc.stop()
            self.chunk_gc = None
        for t in list(self._repair_tasks):
            t.cancel()
        self.retry.stop()
        if self.scheduler:
            await self.scheduler.stop()
        if self._runner:
            await self._runner.cleanup()
        if self._tracker_client:
            await self._tracker_client.close()
        if self._health_http:
            await self._health_http.close()
        if self.server:
            await self.server.close_heal_cluster()
        # After the listeners are down: no handler can enqueue anymore.
        # Reap the cancelled poll task BEFORE releasing the sqlite
        # handle -- cancellation lands at its next await, and a close
        # under a still-running run_once strands the task (the soak
        # tripwire caught exactly this race).
        await self.retry.reap()
        self.retry.close()
        # LAST: the clean-shutdown stamp bounds the next boot's fsck
        # crash-window verify to blobs written after this instant.
        await asyncio.to_thread(write_clean_shutdown, self.store)


class BuildIndexNode:
    """Build-index: tag server + durable replication."""

    def __init__(
        self,
        store_root: str,
        host: str = "127.0.0.1",
        port: int = 0,
        backends: BackendManager | None = None,
        remotes: list[str] | None = None,
        origin_cluster: ClusterClient | None = None,
        ssl_context=None,
        immutable_tags: bool = False,
        task_timeout_seconds: float = 1800.0,
    ):
        from kraken_tpu.buildindex.server import TagServer
        from kraken_tpu.buildindex.tagstore import TagStore

        self.host = host
        self.port = port
        self.retry = RetryManager(
            TaskStore(f"{store_root}/retry.db"),
            task_timeout_seconds=task_timeout_seconds,
        )
        self.store = TagStore(
            f"{store_root}/tags", backends=backends, retry=self.retry
        )
        self.server = TagServer(
            self.store,
            retry=self.retry,
            remotes=remotes,
            origin_cluster=origin_cluster,
            immutable=immutable_tags,
        )
        self.ssl_context = ssl_context
        self._runner: Optional[web.AppRunner] = None
        self._refresh_task: Optional[asyncio.Task] = None

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"

    async def start(self) -> None:
        self._runner, self.port = await _serve(
            self.server.make_app(), self.host, self.port, "build-index",
            ssl_context=self.ssl_context,
        )
        self.retry.start()
        self._refresh_task = asyncio.create_task(_ring_refresh_loop(
            lambda: self.server.origin_cluster, 5.0
        ))

    async def stop(self) -> None:
        if self._refresh_task:
            self._refresh_task.cancel()
        self.retry.stop()
        if self._runner:
            await self._runner.cleanup()
        await self.retry.reap()
        self.retry.close()


class ProxyNode:
    """Proxy: the docker-push registry frontend (write mode)."""

    def __init__(
        self,
        origin_cluster: ClusterClient,
        build_index_addr: str,
        host: str = "127.0.0.1",
        port: int = 0,
        ssl_context=None,
        spool_root: str | None = None,
    ):
        from kraken_tpu.buildindex.server import TagClient
        from kraken_tpu.dockerregistry.registry import RegistryServer
        from kraken_tpu.dockerregistry.transfer import ProxyTransferer

        self.host = host
        self.port = port
        self.origin_cluster = origin_cluster
        self._tag_client = TagClient(build_index_addr)
        # A configured spool_root makes upload sessions durable across
        # proxy restarts (a crashed mid-push resumes); without it both
        # spools fall back to fresh temp dirs.
        upload_dir = os.path.join(spool_root, "uploads") if spool_root else None
        pass_dir = os.path.join(spool_root, "passthrough") if spool_root else None
        self.server = RegistryServer(
            ProxyTransferer(origin_cluster, self._tag_client,
                            spool_dir=pass_dir),
            read_only=False,
            upload_dir=upload_dir,
        )
        self.ssl_context = ssl_context
        self._runner: Optional[web.AppRunner] = None
        self._refresh_task: Optional[asyncio.Task] = None

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"

    async def start(self) -> None:
        self._runner, self.port = await _serve(
            self.server.make_app(), self.host, self.port, "proxy",
            ssl_context=self.ssl_context,
        )
        self._refresh_task = asyncio.create_task(_ring_refresh_loop(
            lambda: self.origin_cluster, 5.0
        ))

    async def stop(self) -> None:
        if self._refresh_task:
            self._refresh_task.cancel()
        if self._runner:
            await self._runner.cleanup()
        await self._tag_client.close()


class AgentNode:
    """Agent: download daemon + agentserver (+ optional docker-registry
    read endpoint when a build-index address is configured)."""

    def __init__(
        self,
        store_root: str,
        tracker_addr: str,
        host: str = "127.0.0.1",
        http_port: int = 0,
        p2p_port: int = 0,
        registry_port: int = 0,
        build_index_addr: str = "",
        hasher: str = "cpu",
        hash_workers: int = 1,
        cleanup: CleanupConfig | None = None,
        scheduler_config: SchedulerConfig | None = None,
        p2p_bandwidth: dict | None = None,
        ssl_context=None,
        tag_cache_ttl: float = 0.0,
        durability: str = "rename",
        registry_strict_accept: bool = False,
        scrub: dict | ScrubConfig | None = None,
        fsck: bool = True,
        recipe_cache_ttl_seconds: float = 60.0,
        rpc: dict | RPCConfig | None = None,
        resources: dict | ResourcesConfig | None = None,
        trace: dict | TraceConfig | None = None,
        delta: dict | DeltaConfig | None = None,
        profiling: dict | ProfilerConfig | None = None,
        chunkstore: dict | ChunkStoreConfig | None = None,
        slo: dict | SLOConfig | None = None,
        canary: dict | CanaryConfig | None = None,
        ingest: dict | IngestConfig | None = None,
        pex: dict | PexConfig | None = None,
    ):
        self.host = host
        self.http_port = http_port
        self.p2p_port = p2p_port
        self.registry_port = registry_port
        # Agents run no ingest pipeline; the YAML ``ingest:`` section here
        # carries the ROBUSTNESS knobs only (resume gates whether fsck
        # preserves journaled upload state on the shared store layer).
        self.ingest_config = None if ingest is None else _ingest_config(ingest)
        # Manifest Accept negotiation: strict mode 406s clients pinned to
        # types we don't hold; default serves the stored bytes like the
        # reference (old docker clients regress under strict -- ADVICE r5).
        self.registry_strict_accept = registry_strict_accept
        self.build_index_addr = build_index_addr
        self.tracker_addr = tracker_addr
        self.store = CAStore(store_root, durability=durability)
        # Content-addressed chunk tier (store/chunkstore.py): completed
        # pulls whose recipe the delta planner fetched convert to
        # manifest + refcounted chunks -- agents are the tier's FIRST
        # rollout ring (OPERATIONS.md runbook). YAML `chunkstore:`;
        # shipped OFF; SIGHUP live-reloads. Attached before fsck.
        self.chunkstore_config = _chunkstore_config(chunkstore)
        self.chunk_gc: Optional[ChunkGC] = None
        _sync_chunkstore(self)
        # The verifier takes its batching rule from the hasher's kind:
        # CPU verify flushes every tick, any number at once; device verify
        # keeps at most two sections in flight, one on the chip and one
        # staging on the host, and sends what arrived while both were out
        # as the next (BatchedVerifier).
        # hash_workers: the same host hash pool the origin uses, here
        # feeding BatchedVerifier.hash_batch -- a multi-core agent
        # verifies a piece batch across cores instead of one. Only >= 2
        # buys anything on an agent: hash_batch takes the inline path
        # below 2 workers (core/hasher.py), and agents have no stream-
        # submit tier to keep a 1-worker pool busy -- building one just
        # parks an idle thread behind misleading pool gauges.
        self.verifier = BatchedVerifier(
            hasher=get_hasher(
                hasher, workers=hash_workers if hash_workers >= 2 else 0
            ),
        )
        self.cleanup = (
            CleanupManager(self.store, cleanup, after_evict=self._after_evict)
            if cleanup
            else None
        )
        self.scheduler_config = scheduler_config
        self.p2p_bandwidth = (
            BandwidthLimiter(**p2p_bandwidth) if p2p_bandwidth else None
        )
        self.ssl_context = ssl_context
        # 0 disables tag caching. Only raise this when the cluster declares
        # immutable_tags on the build-index: with mutable tags, a positive
        # cache serves a re-pointed tag's OLD digest for up to the TTL.
        self.tag_cache_ttl = tag_cache_ttl
        # Agent self-healing: fsck sweeps crash debris; the scrubber
        # quarantines rot and unseeds it. No heal task here -- an agent
        # cache miss already re-pulls through the swarm on demand, and
        # agents never write namespace sidecars (expect_namespace=False).
        self.fsck_enabled = fsck
        self.scrub_config = (
            ScrubConfig(**scrub) if isinstance(scrub, dict) else scrub
        )
        # Agent-side TTL cache for delta-plane control reads (recipes +
        # /similar): a tracker failover must never re-fetch a recipe
        # this agent just had. Recipes are CAS-immutable, so only
        # /similar pays staleness (bounded by this TTL). 0 disables.
        self.recipe_cache_ttl = recipe_cache_ttl_seconds
        # Overload & degradation knobs (YAML `rpc:`; live-reloadable).
        self.rpc = _rpc_config(rpc)
        # Resource sentinel budgets (YAML `resources:`; live-reloadable).
        self.resources_config = _resources_config(resources)
        # Tracing knobs (YAML `trace:`; live-reloadable; utils/trace.py).
        self.trace_config = _trace_config(trace)
        # Delta-transfer plane (p2p/delta.py): on a pull, copy the chunks
        # a locally-held near-duplicate blob already has and fetch only
        # the rest (origin byte ranges + swarm pieces). Shipped OFF;
        # YAML `delta:`; SIGHUP live-reloads (the planner is always
        # constructed so a reload can enable it without a restart).
        self.delta_config = _delta_config(delta)
        self.delta: Optional[DeltaPlanner] = None
        # Continuous profiling plane (utils/profiler.py); YAML
        # `profiling:`; SIGHUP live-reloads.
        self.profiling_config = _profiling_config(profiling)
        # SLO plane (utils/slo.py): pull/announce SLIs feed the
        # burn-rate evaluators; /debug/slo on the mux. YAML `slo:`.
        self.slo_config = _slo_config(slo)
        # Synthetic canary prober (utils/canary.py): periodic seeded
        # pull through the real stack so the SLO plane stays fed at
        # zero user traffic. Shipped OFF (needs `canary.origins`);
        # SIGHUP live-reloads (the prober is always constructed so a
        # reload can enable it without a restart).
        self.canary_config = _canary_config(canary)
        self.canary: Optional[CanaryProber] = None
        # Gossip peer exchange (p2p/pex.py): conns piggyback peer
        # deltas so the swarm survives total tracker loss; known peers
        # persist to <store>/peercache.json and seed redials across a
        # restart. YAML `pex:`; shipped ON; SIGHUP live-reloads every
        # knob except the peercache path (fixed at startup).
        self.pex_config = _pex_config(pex)
        self.loop_monitor: Optional[LoopLagMonitor] = None
        self.sentinel: Optional[ResourceSentinel] = None
        self.scrubber: Optional[Scrubber] = None
        self.fsck_report = None
        self.scheduler: Optional[Scheduler] = None
        self.server: Optional[AgentServer] = None
        self._runner: Optional[web.AppRunner] = None
        self._registry_runner: Optional[web.AppRunner] = None
        self._tracker_client: Optional[TrackerClient] = None
        self._tag_client = None
        self._cleanup_task: Optional[asyncio.Task] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.http_port}"

    def _after_evict(self, d: Digest) -> None:
        """Cleanup worker thread, post-delete: an evicted blob must leave
        the swarm (and must already be gone, or an inbound handshake could
        resurrect the control)."""
        loop, sched = self._loop, self.scheduler
        if loop is not None and sched is not None:
            loop.call_soon_threadsafe(sched.unseed, d)

    def _on_scrub_corrupt(self, d: Digest, ns: str) -> None:
        """Scrub-task context (event loop), blob already quarantined: stop
        advertising it to the swarm. The next local read is a cache miss
        and re-pulls verified pieces on demand -- the agent's heal path."""
        if self.scheduler is not None:
            self.scheduler.unseed(d)

    @property
    def registry_addr(self) -> str | None:
        """Where the docker-registry read endpoint is served, or None when
        it is not enabled (no build-index configured)."""
        if self._registry_runner is None:
            return None
        return f"{self.host}:{self.registry_port}"

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        # Trace config before the scheduler forks any seed-serve worker
        # (the fork inherits the applied tracer config).
        _apply_trace("agent", self.trace_config, self.store.root)
        # Profiling config before the fork; loop-lag heartbeat before
        # the sentinel (which probes its p99).
        self.profiling_config = _apply_profiling(
            "agent", self.profiling_config, self.store.root
        )
        _apply_slo("agent", self.slo_config)
        _sync_loop_monitor(self, "agent")
        if self.fsck_enabled:
            self.fsck_report = await asyncio.to_thread(
                run_fsck,
                self.store,
                upload_ttl_seconds=(
                    self.cleanup.config.upload_ttl_seconds
                    if self.cleanup
                    else 6 * 3600
                ),
                expect_namespace=False,
                resume=(
                    self.ingest_config.resume
                    if self.ingest_config is not None
                    else True
                ),
            )
        factory = PeerIDFactory(
            PeerIDFactory.ADDR_HASH if self.p2p_port else PeerIDFactory.RANDOM
        )
        peer_id = factory.create(self.host, self.p2p_port)
        # Comma-separated tracker list -> sharded fleet client with
        # failover (tracker/client.py). The recipe/similar TTL cache
        # rides the client so a tracker failover never re-fetches a
        # recipe this agent just had.
        self._tracker_client = make_tracker_client(
            self.tracker_addr, peer_id, self.host, 0,
            announce_timeout_seconds=self.rpc.announce_timeout_seconds,
            request_deadline_seconds=self.rpc.request_deadline_seconds,
            hedge_delay_seconds=self.rpc.hedge_delay_seconds,
            recipe_cache_ttl_seconds=self.recipe_cache_ttl,
        )
        archive = AgentTorrentArchive(self.store, self.verifier)
        # Always constructed (cheap: one idle HTTP client); the config's
        # enabled flag gates every prefill, so a SIGHUP can turn delta on
        # without a restart.
        self.delta = DeltaPlanner(
            self.store, archive, self._tracker_client, self.delta_config
        )
        self.scheduler = Scheduler(
            peer_id=peer_id,
            ip=self.host,
            port=self.p2p_port,
            archive=archive,
            metainfo_client=self._tracker_client,
            announce_client=self._tracker_client,
            config=self.scheduler_config,
            bandwidth=self.p2p_bandwidth,
            delta=self.delta,
            pex=self.pex_config,
            peercache_path=os.path.join(self.store.root, "peercache.json"),
        )
        await self.scheduler.start()
        self._tracker_client.port = self.scheduler.port
        self.server = AgentServer(
            self.store, self.scheduler, cleanup=self.cleanup
        )
        self._runner, self.http_port = await _serve(
            self.server.make_app(), self.host, self.http_port, "agent",
            ssl_context=self.ssl_context,
        )
        if self.cleanup is not None:
            self._cleanup_task = asyncio.create_task(
                _cleanup_loop(self.cleanup)
            )
        if self.scrub_config is not None:
            self.scrubber = Scrubber(
                self.store,
                self.scrub_config,
                hasher=self.verifier.hasher,
                on_corrupt=self._on_scrub_corrupt,
            )
            self.scrubber.start()
        self.sentinel = _start_sentinel(self, "agent")
        _sync_chunk_gc(self)
        # Canary prober: started always (one sleeping task), probing
        # only while canary.enabled -- so SIGHUP can flip it on live.
        self.canary = CanaryProber(
            self.store, self.scheduler, self.canary_config,
            node=f"agent-{self.host}",
        )
        self.canary.start()
        if self.build_index_addr:
            from kraken_tpu.buildindex.server import TagClient
            from kraken_tpu.dockerregistry.registry import RegistryServer
            from kraken_tpu.dockerregistry.transfer import ReadOnlyTransferer

            self._tag_client = TagClient(self.build_index_addr)
            registry = RegistryServer(
                ReadOnlyTransferer(
                    self.store, self.scheduler, self._tag_client,
                    tag_cache_ttl=self.tag_cache_ttl,
                ),
                read_only=True,
                strict_accept=self.registry_strict_accept,
            )
            self._registry_runner, self.registry_port = await _serve(
                registry.make_app(), self.host, self.registry_port,
                "agent-registry", ssl_context=self.ssl_context,
            )

    def reload(self, cfg: dict) -> None:
        """Apply a re-read config's ``scheduler:``, ``tracker:``, and
        ``rpc:`` sections live (SIGHUP)."""
        if self.scheduler is not None and cfg.get("scheduler") is not None:
            self.scheduler.reload(SchedulerConfig.from_dict(cfg["scheduler"]))
        _reload_tracker_addrs(self, cfg.get("tracker"))
        if cfg.get("rpc") is not None:
            self.rpc = _rpc_config(cfg["rpc"])
            if self._tracker_client is not None:
                self._tracker_client.announce_timeout = (
                    self.rpc.announce_timeout_seconds
                )
                if hasattr(self._tracker_client, "request_deadline"):
                    self._tracker_client.request_deadline = (
                        self.rpc.request_deadline_seconds
                    )
                    self._tracker_client.hedge_delay = (
                        self.rpc.hedge_delay_seconds or None
                    )
            _log.info("rpc config reloaded", extra={"node": self.addr})
        if cfg.get("resources") is not None:
            self.resources_config = _resources_config(cfg["resources"])
            if self.sentinel is not None:
                self.sentinel.apply(self.resources_config)
        if cfg.get("trace") is not None:
            self.trace_config = _trace_config(cfg["trace"])
            _apply_trace("agent", self.trace_config, self.store.root)
        if cfg.get("delta") is not None:
            # Live enable/disable + knob swap: the planner re-reads its
            # config object on every prefill.
            self.delta_config = _delta_config(cfg["delta"])
            if self.delta is not None:
                self.delta.config = self.delta_config
        if cfg.get("profiling") is not None:
            self.profiling_config = _apply_profiling(
                "agent", _profiling_config(cfg["profiling"]),
                self.store.root,
            )
            _sync_loop_monitor(self, "agent")
        if cfg.get("chunkstore") is not None:
            # Agents-first rollout: SIGHUP-enable attaches the tier and
            # converts from the next completed pull on; disable stops
            # new conversions, manifest-backed blobs keep serving.
            self.chunkstore_config = _chunkstore_config(cfg["chunkstore"])
            _sync_chunkstore(self)
            _sync_chunk_gc(self)
        if cfg.get("slo") is not None:
            self.slo_config = _slo_config(cfg["slo"])
            _apply_slo("agent", self.slo_config)
        if cfg.get("ingest") is not None:
            # Robustness knobs only on agents (no pipeline): takes
            # effect at the next fsck/sweep that consults it.
            self.ingest_config = _ingest_config(cfg["ingest"])
        if cfg.get("canary") is not None:
            # Live enable/disable + knob swap: the prober loop re-reads
            # its config object every tick.
            self.canary_config = _canary_config(cfg["canary"])
            if self.canary is not None:
                self.canary.config = self.canary_config
        if cfg.get("pex") is not None:
            # Gossip cadence/budgets/TTLs swap live; the peercache path
            # is fixed at startup (a moved cache is a fresh cache).
            self.pex_config = _pex_config(cfg["pex"])
            if self.scheduler is not None:
                self.scheduler.reload_pex(self.pex_config)

    async def drain(self, timeout: float | None = None) -> None:
        """Lameduck drain (SIGTERM path): stop announcing, fail /health,
        refuse new swarm pulls and p2p conns; in-flight downloads and
        pieces finish up to ``drain_timeout``. :meth:`stop` follows."""
        await _drain_node(
            self.server, self.scheduler,
            self.rpc.drain_timeout_seconds if timeout is None else timeout,
            "agent",
        )

    async def stop(self) -> None:
        # Refusal-before-teardown (see OriginNode.stop).
        if self.server is not None:
            self.server.enter_lameduck()
        elif self.scheduler is not None:
            self.scheduler.enter_lameduck()
        if self._cleanup_task:
            self._cleanup_task.cancel()
        if self.sentinel:
            self.sentinel.stop()
        if self.loop_monitor:
            self.loop_monitor.stop()
        if self.scrubber:
            self.scrubber.stop()
        if self.chunk_gc:
            self.chunk_gc.stop()
            self.chunk_gc = None
        if self.canary:
            # Before the scheduler stops: the reap sweep unseeds its
            # canary blobs through it.
            await self.canary.stop()
            self.canary = None
        if self.scheduler:
            await self.scheduler.stop()
        if self._runner:
            await self._runner.cleanup()
        if self._registry_runner:
            await self._registry_runner.cleanup()
        if self._tracker_client:
            await self._tracker_client.close()
        if self._tag_client:
            await self._tag_client.close()
        if self.delta:
            await self.delta.close()
        # LAST: bound the next boot's fsck crash-window verify.
        await asyncio.to_thread(write_clean_shutdown, self.store)
