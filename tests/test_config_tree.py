"""The shipped config tree must stay loadable and internally consistent.

Config rot is silent: a renamed constructor kwarg or a typo'd YAML key in
`config/` breaks production boots without failing any code-path test.
This loads every shipped file through the SAME loader the CLI uses
(extends-merge included) and cross-checks the keys each component file
carries against what the CLI/assembly actually consume.
"""

import inspect
import os

from kraken_tpu.configutil import load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "config")

# Keys the CLI layer itself consumes (kraken_tpu/cli.py `cfg.get` /
# `pick(...)` sites) rather than forwarding to a constructor kwarg.
CLI_KEYS = {
    "host", "port", "store", "tracker", "p2p_port", "hasher",
    "cluster", "cluster_dns", "self_addr", "max_replica", "backends",
    "cleanup", "tls", "tls_client", "scheduler", "origins",
    "announce_interval_seconds", "peer_ttl_seconds", "peerstore_redis",
    "registry_port", "build_index", "spool", "remotes", "dedup_index",
    "dedup_budget_bytes", "extends", "immutable_tags", "p2p_bandwidth",
    "tag_cache_ttl", "durability", "dedup_low_j_bands", "hash_workers",
    "registry_strict_accept", "failpoints", "scrub", "fsck",
    "task_timeout_seconds", "rpc", "resources", "trace", "delta",
    "profiling", "fleet", "chunkstore", "slo", "canary", "ingest",
    "pex", "quorum",
}


def _component_files():
    for comp in ("agent", "origin", "tracker", "proxy", "build-index"):
        d = os.path.join(CONFIG, comp)
        for f in sorted(os.listdir(d)):
            if f.endswith(".yaml"):
                yield comp, os.path.join(d, f)


def test_every_shipped_config_loads_with_extends():
    seen = 0
    for comp, path in _component_files():
        cfg = load_config(path)
        assert isinstance(cfg, dict) and cfg, path
        # The extends-merge must have pulled the shared base in.
        assert "host" in cfg, f"{path}: base.yaml extends-merge missing"
        seen += 1
    assert seen >= 5


def test_shipped_config_keys_are_consumed():
    """Every top-level key in every shipped file must be one the CLI
    reads -- an unknown key is a typo or a renamed knob, and YAML has no
    other way to tell the operator."""
    for comp, path in _component_files():
        cfg = load_config(path)
        unknown = set(cfg) - CLI_KEYS
        assert not unknown, f"{path}: unconsumed keys {sorted(unknown)}"


def test_cleanup_watermarks_ordered():
    for comp, path in _component_files():
        cfg = load_config(path)
        cl = cfg.get("cleanup")
        if not cl:
            continue
        assert cl["low_watermark_bytes"] < cl["high_watermark_bytes"], path


def test_scrub_sections_construct_scrub_config():
    """Every shipped `scrub:` section must map 1:1 onto ScrubConfig
    kwargs -- the CLI constructs it with ScrubConfig(**section), so a
    typo'd knob is a boot-time TypeError in production."""
    import dataclasses

    from kraken_tpu.store.scrub import ScrubConfig

    fields = {f.name for f in dataclasses.fields(ScrubConfig)}
    seen = 0
    for comp, path in _component_files():
        sc = load_config(path).get("scrub")
        if not sc:
            continue
        assert set(sc) <= fields, f"{path}: unknown scrub keys {set(sc) - fields}"
        cfg = ScrubConfig(**sc)
        assert cfg.bytes_per_second >= 0 and cfg.interval_seconds > 0, path
        seen += 1
    assert seen >= 2  # origin + agent ship scrub enabled


def test_scheduler_sections_construct_scheduler_config():
    """Every shipped `scheduler:` section (wire_send_batch,
    bufpool_budget_mb, pacing knobs...) must map onto SchedulerConfig
    kwargs through the same from_dict the CLI/assembly use -- a typo'd
    wire knob must fail here, not at production boot."""
    from kraken_tpu.p2p.scheduler import SchedulerConfig

    seen = 0
    workers_shipped = 0
    for comp, path in _component_files():
        sc = load_config(path).get("scheduler")
        if not sc:
            continue
        cfg = SchedulerConfig.from_dict(sc)  # raises on unknown keys
        assert cfg.wire_send_batch >= 1, path
        assert cfg.bufpool_budget_mb >= 0, path
        # Multi-core data plane (round 8): the knob must construct, and
        # the SHIPPED default must be 0 -- forking serve shards is an
        # explicit operator decision, never a config-refresh surprise.
        assert cfg.data_plane_workers >= 0, path
        if "data_plane_workers" in sc:
            assert cfg.data_plane_workers == 0, (
                f"{path}: shipped data_plane_workers must default to 0"
            )
            workers_shipped += 1
        # Multi-core LEECH plane (round 19): same contract -- the knob
        # constructs, ships 0 (forking download pumps is an explicit
        # operator decision), and the ring budget stays sane.
        assert cfg.leech_workers >= 0, path
        assert cfg.leech_ring_mb >= 4, path  # must hold >= one 4 MiB slot
        if "leech_workers" in sc:
            assert cfg.leech_workers == 0, (
                f"{path}: shipped leech_workers must default to 0"
            )
        seen += 1
    assert seen >= 2  # origin + agent ship the wire-plane knobs
    assert workers_shipped >= 2  # origin + agent register the knob
    # The agent yaml registers the leech knobs (origins drop them).
    agent_sc = load_config("config/agent/base.yaml").get("scheduler") or {}
    assert "leech_workers" in agent_sc and "leech_ring_mb" in agent_sc


def test_rpc_sections_construct_rpc_config():
    """Every shipped `rpc:` section (deadlines, hedge delay, brown-out
    threshold, drain timeout) must map onto RPCConfig through the same
    from_dict the CLI/assembly use -- a typo'd degradation knob must
    fail here, not at production boot."""
    from kraken_tpu.utils.deadline import RPCConfig

    seen = 0
    for comp, path in _component_files():
        rc = load_config(path).get("rpc")
        if not rc:
            continue
        cfg = RPCConfig.from_dict(rc)  # raises on unknown keys
        assert cfg.announce_timeout_seconds > 0, path
        assert cfg.drain_timeout_seconds > 0, path
        assert cfg.request_deadline_seconds > 0, path
        seen += 1
    assert seen >= 3  # agent + origin + tracker ship the rpc knobs


def test_resources_sections_construct_resources_config():
    """Every shipped `resources:` section (sentinel period + budgets)
    must map onto ResourcesConfig through the same from_dict the
    CLI/assembly use -- a typo'd budget knob must fail here, not at
    production boot (where it would silently disable the sentinel's
    teeth)."""
    from kraken_tpu.utils.resources import ResourcesConfig

    seen = 0
    for comp, path in _component_files():
        rc = load_config(path).get("resources")
        if not rc:
            continue
        cfg = ResourcesConfig.from_dict(rc)  # raises on unknown keys
        assert cfg.interval_seconds > 0, path
        assert cfg.breach_streak >= 1, path
        # Shipped defaults must be observe-only: budgets that drain by
        # default would shed healthy nodes on under-provisioned rigs.
        assert cfg.drain_on_breach is False, path
        seen += 1
    assert seen >= 2  # agent + origin ship the sentinel knobs


def test_trace_sections_construct_trace_config():
    """Every shipped `trace:` section must map onto TraceConfig through
    the same from_dict the CLI/assembly use -- a typo'd tracing knob
    must fail here, not at production boot. The shipped defaults must
    stay SAMPLED-DOWN: a config refresh that ships sample_rate 1.0
    would tax every pull's data plane fleet-wide (the overhead band in
    test_data_plane_band.py is measured at the shipped rate)."""
    from kraken_tpu.utils.trace import TraceConfig

    seen = 0
    for comp, path in _component_files():
        tc = load_config(path).get("trace")
        if not tc:
            continue
        cfg = TraceConfig.from_dict(tc)  # raises on unknown keys
        assert cfg.enabled is True, path
        assert 0.0 < cfg.sample_rate <= 0.05, (
            f"{path}: shipped sample_rate must stay sampled-down"
        )
        assert cfg.slow_threshold_seconds > 0, path
        assert cfg.keep_spans >= 256, path
        assert cfg.dump_min_interval_seconds > 0, path
        # dump_dir ships unset: assembly defaults it under the node's
        # store root, and store-less trackers stay file-dump-free.
        assert cfg.dump_dir == "", path
        seen += 1
    assert seen >= 3  # agent + origin + tracker ship the trace knobs


def test_delta_sections_construct_delta_config():
    """Every shipped `delta:` section must map onto DeltaConfig through
    the same from_dict the CLI/assembly use -- a typo'd knob must fail
    here, not at production boot. The shipped default must stay OFF on
    BOTH sides: delta is a rollout decision (origins serve recipes
    first, agents canary after -- OPERATIONS.md runbook), never a
    config-refresh surprise."""
    from kraken_tpu.p2p.delta import DeltaConfig

    seen = 0
    for comp, path in _component_files():
        dc = load_config(path).get("delta")
        if dc is None:
            continue
        cfg = DeltaConfig.from_dict(dc)  # raises on unknown keys
        assert cfg.enabled is False, (
            f"{path}: shipped delta.enabled must stay false"
        )
        assert cfg.min_blob_bytes >= 0, path
        assert cfg.max_bases >= 1, path
        assert 0.0 <= cfg.min_jaccard <= 1.0, path
        assert 0.0 <= cfg.min_piece_cover <= 1.0, path
        seen += 1
    assert seen >= 2  # agent + origin register the delta knobs


def test_chunkstore_sections_construct_chunkstore_config():
    """Every shipped `chunkstore:` section must map onto
    ChunkStoreConfig through the same from_dict the CLI/assembly use --
    a typo'd knob must fail here, not at production boot. The shipped
    default must stay OFF on BOTH components: converting blobs to
    manifests is a rollout decision (agents first, origins after soak
    -- OPERATIONS.md runbook), never a config-refresh surprise."""
    from kraken_tpu.store.chunkstore import ChunkStoreConfig

    seen = 0
    for comp, path in _component_files():
        cc = load_config(path).get("chunkstore")
        if cc is None:
            continue
        cfg = ChunkStoreConfig.from_dict(cc)  # raises on unknown keys
        assert cfg.enabled is False, (
            f"{path}: shipped chunkstore.enabled must stay false"
        )
        assert cfg.min_blob_bytes >= 0, path
        assert cfg.gc_interval_seconds > 0, path
        assert cfg.gc_bytes_per_second >= 0, path
        seen += 1
    assert seen >= 2  # agent + origin register the chunkstore knobs


def test_profiling_sections_construct_profiler_config():
    """Every shipped `profiling:` section must map onto ProfilerConfig
    through the same from_dict the CLI/assembly use -- a typo'd knob
    must fail here, not at production boot. The shipped sample rate
    must stay LOW: the profiler-on overhead band in
    test_data_plane_band.py is measured at the shipped hz, and a config
    refresh that ships 250 Hz would tax every process fleet-wide."""
    from kraken_tpu.utils.profiler import ProfilerConfig

    seen = 0
    for comp, path in _component_files():
        pc = load_config(path).get("profiling")
        if not pc:
            continue
        cfg = ProfilerConfig.from_dict(pc)  # raises on unknown keys
        assert cfg.enabled is True, path
        assert 0.0 < cfg.hz <= 50.0, (
            f"{path}: shipped profiling.hz must stay sampled-down"
            " (the overhead band is measured at the shipped rate)"
        )
        assert cfg.window_seconds > 0 and cfg.keep_windows >= 2, path
        assert cfg.loop_lag_interval_seconds > 0, path
        assert cfg.loop_lag_threshold_seconds > 0, path
        assert cfg.dump_min_interval_seconds > 0, path
        # dump_dir ships unset: assembly defaults it beside the trace
        # dumps under the node's store root; trackers stay file-free.
        assert cfg.dump_dir == "", path
        seen += 1
    assert seen >= 3  # agent + origin + tracker ship the profiling knobs


def test_slo_sections_construct_slo_config():
    """Every shipped `slo:` section must map onto SLOConfig through the
    same from_dict the CLI/assembly use -- a typo'd objective or window
    knob must fail here, not at production boot (where it would
    silently disable the paging plane)."""
    from kraken_tpu.utils.slo import SLOConfig

    seen = 0
    for comp, path in _component_files():
        sc = load_config(path).get("slo")
        if not sc:
            continue
        cfg = SLOConfig.from_dict(sc)  # raises on unknown keys
        assert cfg.enabled is True, path
        assert cfg.eval_interval_seconds > 0, path
        assert cfg.bucket_seconds > 0, path
        # The shipped window pairs must stay the SRE-workbook shape:
        # page strictly faster + hotter than ticket, AND-conditions
        # well-formed (short <= long).
        assert cfg.fast.short_seconds <= cfg.fast.long_seconds, path
        assert cfg.slow.short_seconds <= cfg.slow.long_seconds, path
        assert cfg.fast.burn_rate > cfg.slow.burn_rate, path
        for sli, obj in cfg.objective_map.items():
            assert 0.0 < obj.target < 1.0, (path, sli)
        seen += 1
    assert seen >= 3  # agent + origin + tracker ship the slo knobs


def test_canary_sections_construct_canary_config():
    """Every shipped `canary:` section must map onto CanaryConfig
    through the same from_dict the CLI/assembly use. The shipped
    default must stay OFF: probing needs `origins` pointed at the
    cluster and is a rollout decision, never a config-refresh
    surprise."""
    from kraken_tpu.utils.canary import CanaryConfig

    seen = 0
    for comp, path in _component_files():
        cc = load_config(path).get("canary")
        if cc is None:
            continue
        cfg = CanaryConfig.from_dict(cc)  # raises on unknown keys
        assert cfg.enabled is False, (
            f"{path}: shipped canary.enabled must stay false"
        )
        assert cfg.interval_seconds >= 10.0, (
            f"{path}: shipped canary cadence must stay modest (the"
            " data-plane bands are measured without canary load)"
        )
        assert 0 < cfg.blob_bytes <= 4 * 1024 * 1024, path
        assert cfg.pull_timeout_seconds > 0, path
        assert cfg.ttl_seconds > cfg.interval_seconds, path
        seen += 1
    assert seen >= 1  # the agent registers the canary knobs


def test_pex_sections_construct_pex_config():
    """Every shipped `pex:` section must map onto PexConfig through the
    same from_dict the CLI/assembly use -- a typo'd knob must fail here,
    not at production boot. The shipped defaults ship the gossip plane
    ON (receive AND send: a fleet that only listens never bootstraps
    through a tracker outage) but with conservative send budgets, and
    the peercache ON so restarts rejoin the swarm tracker-free."""
    from kraken_tpu.p2p.pex import PexConfig

    seen = 0
    for comp, path in _component_files():
        pc = load_config(path).get("pex")
        if pc is None:
            continue
        cfg = PexConfig.from_dict(pc)  # raises on unknown keys
        assert cfg.enabled is True, (
            f"{path}: shipped pex.enabled must stay ON (tracker-outage"
            " survival is the point -- docs/OPERATIONS.md 'Tracker"
            " outage survival')"
        )
        assert cfg.send_enabled is True, (
            f"{path}: shipped pex.send_enabled must stay ON (a"
            " receive-only fleet has nothing to receive)"
        )
        assert cfg.interval_seconds >= 10.0, (
            f"{path}: shipped gossip cadence must stay modest (the"
            " data-plane bands are measured with gossip on)"
        )
        assert 1 <= cfg.max_peers_per_message <= 64, (
            f"{path}: shipped send budget must stay conservative"
        )
        assert cfg.dial_rate > 0 and cfg.dial_burst >= 1, path
        assert cfg.seen_ttl_seconds > 0, path
        assert cfg.max_known_peers >= 64, path
        assert cfg.peercache is True, (
            f"{path}: shipped peercache must stay ON (restart-survival"
            " leg of the outage story)"
        )
        assert cfg.peercache_ttl_seconds > cfg.peercache_flush_seconds, path
        seen += 1
    assert seen >= 1  # the agent registers the pex knobs


def test_ingest_sections_construct_ingest_config():
    """Every shipped `ingest:` section must map onto IngestConfig
    through the same from_dict the CLI/assembly use -- a typo'd knob
    must fail here, not at production boot. The shipped defaults must
    stay SAFE: classic double buffering, so a config refresh never
    silently balloons staging RAM."""
    from kraken_tpu.core.ingest import IngestConfig

    seen = 0
    for comp, path in _component_files():
        ic = load_config(path).get("ingest")
        if ic is None:
            continue
        cfg = IngestConfig.from_dict(ic)  # raises on unknown keys
        assert cfg.windows_in_flight == 2, (
            f"{path}: shipped windows_in_flight must stay 2 (double"
            " buffering; staging RAM scales with it)"
        )
        assert 1 << 20 <= cfg.window_bytes <= 1 << 30, path
        assert cfg.resume is True, (
            f"{path}: shipped resume must stay ON (pure robustness --"
            " journaled sessions survive origin crashes; flipping it off"
            " is a per-cluster opt-out, not a shipped default)"
        )
        assert cfg.serve_while_ingest is False, (
            f"{path}: shipped serve_while_ingest must stay OFF (serving"
            " from the upload spool pre-commit is a rollout step --"
            " docs/OPERATIONS.md runbook)"
        )
        seen += 1
    assert seen >= 2  # origin AND agent register the ingest knobs


def test_quorum_sections_construct_quorum_config():
    """Every shipped `quorum:` section must map onto QuorumConfig
    through the same from_dict the CLI/assembly use -- a typo'd knob
    must fail here, not at production boot. The shipped default must
    stay `write_quorum: 1` (classic async replication): gating acks on
    replica round-trips is a per-cluster durability/latency trade the
    operator makes deliberately (docs/OPERATIONS.md 'Write
    durability'), never a config-refresh surprise."""
    from kraken_tpu.origin.server import QuorumConfig

    seen = 0
    for comp, path in _component_files():
        qc = load_config(path).get("quorum")
        if qc is None:
            continue
        cfg = QuorumConfig.from_dict(qc)  # raises on unknown keys
        assert cfg.write_quorum == 1, (
            f"{path}: shipped write_quorum must stay 1 (quorum acks are"
            " an explicit operator opt-in)"
        )
        assert cfg.hint_ttl_seconds > 0, path
        assert cfg.push_timeout_seconds > 0, path
        seen += 1
    assert seen >= 1  # the origin registers the quorum knobs


def test_cli_keys_match_cli_source():
    """CLI_KEYS drifts too: every key this test whitelists must actually
    appear in cli.py, so deleting a knob there fails here."""
    src = inspect.getsource(__import__("kraken_tpu.cli", fromlist=["x"]))
    for key in CLI_KEYS - {"extends"}:
        assert (
            f'"{key}"' in src or f"'{key}'" in src or f"args.{key}" in src
        ), f"CLI_KEYS lists {key!r} but cli.py never mentions it"
