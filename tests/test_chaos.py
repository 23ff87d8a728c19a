"""Chaos tier: deterministic failure injection through REAL assembled
nodes via the failpoint plane (kraken_tpu/utils/failpoints.py).

Every failure test before this PR hand-monkeypatched one code path; the
reaction paths the system actually sells -- corrupt piece -> peer ban ->
re-pull, ENOSPC mid-PATCH -> clean error + spool reclaim, tracker flap ->
metered announce retry, mid-transfer disconnect -> re-request -- had
never run end-to-end. Here each scenario arms a named failpoint with a
deterministic trigger (seeded RNG, one-shot, every-Nth), drives real
origin/tracker/agent nodes over real TCP, and asserts recovery with
BIT-IDENTITY on every completed pull.

Fast scenarios are unmarked (tier-1 runs them); the probabilistic soak is
``slow``. Everything here carries the ``chaos`` marker.
"""

import asyncio
import json
import os

import pytest

from kraken_tpu.assembly import AgentNode, OriginNode, TrackerNode
from kraken_tpu.core.digest import Digest
from kraken_tpu.origin.client import BlobClient, ClusterClient
from kraken_tpu.origin.metainfogen import PieceLengthConfig
from kraken_tpu.placement import HostList, Ring
from kraken_tpu.utils import failpoints
from kraken_tpu.utils.backoff import Backoff
from kraken_tpu.utils.httputil import HTTPClient, HTTPError
from kraken_tpu.utils.metrics import REGISTRY

pytestmark = pytest.mark.chaos

NS = "chaos"
# 64 KiB pieces so a ~300 KB blob exercises multi-piece transfer paths.
SMALL_PIECES = PieceLengthConfig(table=((0, 64 * 1024),))


@pytest.fixture(autouse=True)
def chaos_plane():
    """Every test starts disarmed and ACKNOWLEDGED (nodes may assemble
    with failpoints armed), and leaves the process-global plane clean --
    a leaked armed failpoint would inject into unrelated tests."""
    failpoints.FAILPOINTS.disarm_all()
    failpoints.allow()
    yield failpoints.FAILPOINTS
    failpoints.FAILPOINTS.disarm_all()
    failpoints.allow(False)


async def _wait_for(cond, timeout=15.0, interval=0.05, msg="condition"):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        if asyncio.get_running_loop().time() > deadline:
            raise AssertionError(f"timed out waiting for {msg}")
        await asyncio.sleep(interval)


def _fired(name: str) -> float:
    return REGISTRY.counter("failpoints_fired_total").value(name=name)


async def _herd(tmp_path, n_agents=1, scheduler_config=None):
    tracker = TrackerNode(announce_interval_seconds=0.1, peer_ttl_seconds=5.0)
    await tracker.start()
    origin = OriginNode(
        store_root=str(tmp_path / "origin"),
        tracker_addr=tracker.addr,
        piece_lengths=SMALL_PIECES,
        dedup=False,
    )
    await origin.start()
    cluster = ClusterClient(
        Ring(HostList(static=[origin.addr]), max_replica=1)
    )
    tracker.server.origin_cluster = cluster
    agents = []
    for i in range(n_agents):
        a = AgentNode(
            store_root=str(tmp_path / f"agent{i}"),
            tracker_addr=tracker.addr,
            scheduler_config=scheduler_config,
        )
        await a.start()
        agents.append(a)
    return tracker, origin, agents, cluster


async def _teardown(tracker, origin, agents, cluster):
    for a in agents:
        await a.stop()
    await origin.stop()
    await cluster.close()
    await tracker.stop()


async def _pull(agent, d: Digest, timeout: float = 60.0) -> bytes:
    http = HTTPClient(timeout_seconds=timeout, retries=0)
    try:
        return await http.get(
            f"http://{agent.addr}/namespace/{NS}/blobs/{d.hex}"
        )
    finally:
        await http.close()


# -- the failpoint registry itself ------------------------------------------


def test_trigger_grammar_and_deterministic_replay():
    r = failpoints.FailpointRegistry()
    assert r.fire("nothing.armed") is None  # disarmed: no-op

    r.arm("a", "once")
    assert r.fire("a") and r.fire("a") is None

    r.arm("b", "every:3")
    assert [bool(r.fire("b")) for _ in range(6)] == [
        False, False, True, False, False, True,
    ]

    # Seeded probability replays bit-for-bit across arms.
    r.arm("c", "prob:0.5+seed:7")
    seq1 = [bool(r.fire("c")) for _ in range(32)]
    r.arm("c", "prob:0.5+seed:7")
    seq2 = [bool(r.fire("c")) for _ in range(32)]
    assert seq1 == seq2 and any(seq1) and not all(seq1)

    r.arm("d", "always+times:2")
    assert sum(bool(r.fire("d")) for _ in range(5)) == 2

    r.arm("e", "always+delay:250")
    assert abs(r.fire("e").delay_s - 0.25) < 1e-9

    for bad in ("sometimes", "prob:1.5", "every:0", "once+nope:1", "every"):
        with pytest.raises(ValueError):
            r.arm("f", bad)

    r.arm("g", "always")
    r.disarm("g")
    assert r.fire("g") is None


def test_env_arming_is_self_acknowledging():
    n = failpoints.load_from_env(
        {"KRAKEN_FAILPOINTS":
         "castore.write=once, castore.commit = prob:0.25+seed:3"}
    )
    assert n == 2
    assert failpoints.FAILPOINTS.allowed
    snap = failpoints.FAILPOINTS.snapshot()["failpoints"]
    assert snap["castore.write"]["spec"] == "once"
    assert snap["castore.commit"]["spec"] == "prob:0.25+seed:3"
    with pytest.raises(ValueError):
        failpoints.load_from_env({"KRAKEN_FAILPOINTS": "justaname"})
    with pytest.raises(ValueError):
        failpoints.load_from_env(
            {"KRAKEN_FAILPOINTS": "castore.write=bogus:spec"}
        )


@pytest.mark.parametrize(
    "name",
    # A typo, and a site that left with its code (PR 31).
    ["trcker.announce.error", "ingest.window.pack"],
)
def test_env_arming_rejects_undeclared_names(name):
    # The silent-typo hole: an env entry naming a site that is not in
    # KNOWN_FAILPOINTS would inject nothing and still report the chaos
    # run green. Base names validate; @host variants validate by base.
    with pytest.raises(ValueError, match="KNOWN_FAILPOINTS"):
        failpoints.load_from_env(
            {"KRAKEN_FAILPOINTS": f"{name}=once"}
        )
    n = failpoints.load_from_env(
        {"KRAKEN_FAILPOINTS": "rpc.brownout.slow@10.0.0.1:7610=once"}
    )
    assert n == 1
    # Programmatic arming (tests, admin endpoint) stays free-form --
    # but boot refuses env/yaml-sourced unknowns via assert_safe.
    reg = failpoints.FailpointRegistry()
    reg.arm("totally.adhoc", "once")
    reg.allowed = True
    reg.assert_safe("test")  # api-sourced: fine
    with pytest.raises(ValueError, match="KNOWN_FAILPOINTS"):
        reg.arm(name, "once", source="env")
    # Belt-and-braces: an env/yaml-sourced unknown that somehow got
    # armed (older pickle, direct mutation) still fails the boot guard.
    reg.arm(name, "once")
    reg._armed[name].source = "env"
    with pytest.raises(failpoints.FailpointConfigError, match="undeclared"):
        reg.assert_safe("test")


def test_disarmed_by_default_and_boot_guard():
    """Import-time default is a clean, unacknowledged plane, and
    assembly refuses to bind listeners while failpoints are armed
    without the acknowledgement -- a chaos config pasted into prod (or a
    leaked test arm) fails the boot loudly."""
    fresh = failpoints.FailpointRegistry()
    assert fresh.snapshot() == {"allowed": False, "failpoints": {}}

    async def main():
        failpoints.allow(False)
        failpoints.FAILPOINTS.arm("castore.write", "once")
        t = TrackerNode()
        with pytest.raises(failpoints.FailpointConfigError):
            await t.start()
        await t.stop()
        failpoints.allow()  # the deliberate chaos ack: boots fine
        t2 = TrackerNode()
        await t2.start()
        await t2.stop()

    asyncio.run(main())


def test_failpoints_admin_endpoint():
    """The live-node runbook surface: list/arm/disarm with fire counts
    over the metrics mux (docs/OPERATIONS.md)."""

    async def main():
        from aiohttp import web

        from kraken_tpu.utils.metrics import instrument_app

        app = web.Application()
        instrument_app(app, "chaos-admin-test")
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        base = f"http://127.0.0.1:{runner.addresses[0][1]}"
        http = HTTPClient(retries=0)
        try:
            doc = json.loads(await http.get(f"{base}/debug/failpoints"))
            assert doc["failpoints"] == {}
            await http.post(
                f"{base}/debug/failpoints",
                data=json.dumps(
                    {"action": "arm", "name": "chaos.admin.site",
                     "spec": "every:2"}
                ),
            )
            assert failpoints.fire("chaos.admin.site") is None
            assert failpoints.fire("chaos.admin.site")
            doc = json.loads(await http.get(f"{base}/debug/failpoints"))
            entry = doc["failpoints"]["chaos.admin.site"]
            assert entry["hits"] == 2 and entry["fired"] == 1
            # Firing also shows on /metrics.
            text = await http.get(f"{base}/metrics")
            assert b'failpoints_fired_total{name="chaos.admin.site"}' in text
            with pytest.raises(HTTPError) as ei:
                await http.post(
                    f"{base}/debug/failpoints",
                    data=json.dumps({"action": "bogus"}),
                )
            assert ei.value.status == 400
            # Non-string name: rejected (400), never stored -- an int key
            # would TypeError snapshot()'s sorted() and kill this surface.
            with pytest.raises(HTTPError) as ei:
                await http.post(
                    f"{base}/debug/failpoints",
                    data=json.dumps(
                        {"action": "arm", "name": 123, "spec": "once"}
                    ),
                )
            assert ei.value.status == 400
            assert json.loads(await http.get(f"{base}/debug/failpoints"))
            await http.post(
                f"{base}/debug/failpoints",
                data=json.dumps({"action": "disarm_all"}),
            )
            assert failpoints.fire("chaos.admin.site") is None

            # The mux is unauthenticated, so ARMING demands the chaos
            # acknowledgement: without it (and without
            # KRAKEN_FAILPOINTS_ALLOW=1 in the env) the POST is a 403
            # and nothing is armed or allowed. Disarming stays open.
            failpoints.allow(False)
            assert os.environ.get("KRAKEN_FAILPOINTS_ALLOW") != "1"
            with pytest.raises(HTTPError) as ei:
                await http.post(
                    f"{base}/debug/failpoints",
                    data=json.dumps(
                        {"action": "arm", "name": "castore.commit",
                         "spec": "always"}
                    ),
                )
            assert ei.value.status == 403
            assert not failpoints.FAILPOINTS.allowed
            assert failpoints.fire("castore.commit") is None
            await http.post(  # disarm_all needs no ack
                f"{base}/debug/failpoints",
                data=json.dumps({"action": "disarm_all"}),
            )
        finally:
            await http.close()
            await runner.cleanup()

    asyncio.run(main())


# -- httputil failpoints + retry visibility ----------------------------------


def _retries(method: str) -> float:
    return REGISTRY.counter("http_client_retries_total").value(method=method)


def _giveups(method: str) -> float:
    return REGISTRY.counter("http_client_giveups_total").value(method=method)


def test_http_injected_5xx_exhausts_retries_and_is_counted():
    """`httputil.request.error` armed always: every attempt sees a 503,
    the client retries its budget (counted), then gives up (counted +
    one structured WARN). No real server is ever contacted."""

    async def main():
        r0, g0 = _retries("GET"), _giveups("GET")
        failpoints.FAILPOINTS.arm("httputil.request.error", "always")
        http = HTTPClient(
            retries=2, backoff=Backoff(base_seconds=0.001, jitter=0)
        )
        try:
            with pytest.raises(HTTPError) as ei:
                await http.get("http://127.0.0.1:9/failpoint-test")
            assert ei.value.status == 503
        finally:
            await http.close()
        assert _retries("GET") == r0 + 2
        assert _giveups("GET") == g0 + 1

    asyncio.run(main())


def test_http_conn_reset_once_recovers_on_retry():
    async def main():
        from aiohttp import web

        async def ok(request):
            return web.Response(body=b"x" * 64)

        app = web.Application()
        app.router.add_get("/blob", ok)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        base = f"http://127.0.0.1:{runner.addresses[0][1]}"
        http = HTTPClient(
            retries=2, backoff=Backoff(base_seconds=0.001, jitter=0)
        )
        try:
            r0 = _retries("GET")
            failpoints.FAILPOINTS.arm("httputil.request.conn_reset", "once")
            assert await http.get(f"{base}/blob") == b"x" * 64
            assert _retries("GET") == r0 + 1
            # Truncated body: the caller sees the torn prefix (callers
            # must digest/length-check; castore commit would reject it).
            failpoints.FAILPOINTS.arm("httputil.request.truncate_body", "once")
            assert await http.get(f"{base}/blob") == b"x" * 32
        finally:
            await http.close()
            await runner.cleanup()

    asyncio.run(main())


# -- scenario 1: corrupt piece -> peer ban -> pull completes -----------------


def test_corrupt_piece_bans_peer_and_pull_completes(tmp_path):
    """One injected payload corruption: verify fails (PieceError), the
    dispatcher hard-blacklists the corrupting peer, and the pull still
    finishes bit-identical from the remaining healthy peers."""

    async def main():
        tracker, origin, agents, cluster = await _herd(tmp_path, n_agents=2)
        try:
            blob = os.urandom(5 * 64 * 1024 + 1000)  # 6 pieces
            d = Digest.from_bytes(blob)
            oc = BlobClient(origin.addr)
            await oc.upload(NS, d, blob)
            await oc.close()

            # agent0 pulls clean and stays as a second healthy seeder.
            assert await _pull(agents[0], d) == blob

            fired0 = _fired("p2p.conn.recv.corrupt")
            failpoints.FAILPOINTS.arm("p2p.conn.recv.corrupt", "once")
            got = await _pull(agents[1], d)
            assert got == blob  # bit-identical despite the corruption
            assert _fired("p2p.conn.recv.corrupt") == fired0 + 1
            # The corrupting peer was hard-blacklisted on the leecher.
            assert agents[1].scheduler.conn_state.blacklist._entries
        finally:
            await _teardown(tracker, origin, agents, cluster)

    asyncio.run(main())


# -- scenario 2: ENOSPC mid-PATCH -> clean error, spool reclaimed, retry OK --


def test_enospc_mid_patch_clean_error_spool_reclaimed_retry_succeeds(tmp_path):
    async def main():
        from kraken_tpu.store.cleanup import CleanupConfig, CleanupManager

        origin = OriginNode(
            store_root=str(tmp_path / "origin"),
            piece_lengths=SMALL_PIECES,
            dedup=False,
        )
        await origin.start()
        # resume=False pins the LEGACY fail-fast contract (a mid-stream
        # ENOSPC surfaces as a clean 500, never a hang or corrupt blob);
        # test_enospc_mid_patch_resume_heals_transparently covers the
        # resuming client.
        oc = BlobClient(origin.addr, HTTPClient(retries=0), resume=False)
        try:
            blob = os.urandom(3 * 64 * 1024 + 500)
            d = Digest.from_bytes(blob)

            failpoints.FAILPOINTS.arm("origin.patch.write", "once")
            with pytest.raises(HTTPError) as ei:
                await oc.upload(NS, d, blob)
            assert ei.value.status == 500  # clean error, not a hang/corrupt
            assert not origin.store.in_cache(d)

            # The failed upload left its spool file; the wall-clock sweep
            # reclaims it.
            assert os.listdir(origin.store.upload_dir)
            sweeper = CleanupManager(
                origin.store, CleanupConfig(upload_ttl_seconds=0.05)
            )
            await asyncio.sleep(0.11)
            sweeper.run_once()
            assert os.listdir(origin.store.upload_dir) == []

            # Retried upload succeeds and round-trips bit-identical.
            await oc.upload(NS, d, blob)
            assert await oc.download(NS, d) == blob

            # Deferred write error at close (buffered ENOSPC): same
            # contract.
            blob2 = os.urandom(2 * 64 * 1024)
            d2 = Digest.from_bytes(blob2)
            failpoints.FAILPOINTS.arm("origin.patch.close", "once")
            with pytest.raises(HTTPError) as ei2:
                await oc.upload(NS, d2, blob2)
            assert ei2.value.status == 500
            await oc.upload(NS, d2, blob2)
            assert await oc.download(NS, d2) == blob2
        finally:
            await oc.close()
            await origin.stop()

    asyncio.run(main())


# -- scenario 3: tracker flap -> metered announce retry recovers -------------


def test_tracker_flap_metered_announce_retry_recovers(tmp_path):
    async def main():
        tracker, origin, agents, cluster = await _herd(tmp_path, n_agents=1)
        try:
            blob = os.urandom(3 * 64 * 1024)
            d = Digest.from_bytes(blob)
            oc = BlobClient(origin.addr)
            await oc.upload(NS, d, blob)
            await oc.close()

            meter = REGISTRY.counter("announce_failures_total")
            base = meter.value()
            failpoints.FAILPOINTS.arm("tracker.announce.error", "always")
            pull = asyncio.create_task(_pull(agents[0], d))
            # The flap is VISIBLE: announce failures get metered, not
            # swallowed (FailureMeter on the scheduler's announce loop).
            await _wait_for(
                lambda: meter.value() > base,
                timeout=20.0,
                msg="announce failure to be metered",
            )
            assert not pull.done()
            # Tracker recovers: the paced re-announce finds peers and the
            # pull completes bit-identical.
            failpoints.FAILPOINTS.disarm("tracker.announce.error")
            assert await asyncio.wait_for(pull, 40.0) == blob

            # An empty handout (fresh-restarted tracker) is also benign:
            # the leecher just re-announces.
            failpoints.FAILPOINTS.arm("tracker.announce.empty", "always+times:3")
            blob2 = os.urandom(2 * 64 * 1024)
            d2 = Digest.from_bytes(blob2)
            oc2 = BlobClient(origin.addr)
            await oc2.upload(NS, d2, blob2)
            await oc2.close()
            assert await _pull(agents[0], d2) == blob2
        finally:
            await _teardown(tracker, origin, agents, cluster)

    asyncio.run(main())


# -- scenario 4: mid-transfer disconnect -> re-request -> pull finishes ------


def test_mid_transfer_disconnect_rerequests_and_finishes(tmp_path):
    async def main():
        tracker, origin, agents, cluster = await _herd(tmp_path, n_agents=1)
        try:
            blob = os.urandom(6 * 64 * 1024 + 123)  # 7 pieces
            d = Digest.from_bytes(blob)
            oc = BlobClient(origin.addr)
            await oc.upload(NS, d, blob)
            await oc.close()

            fired0 = _fired("p2p.conn.disconnect")
            # First payload frame kills the conn (and discards the
            # frame): the dispatcher must drop the peer without
            # blacklisting, re-announce, re-dial, and re-request the
            # lost piece.
            failpoints.FAILPOINTS.arm("p2p.conn.disconnect", "once")
            got = await _pull(agents[0], d)
            assert got == blob
            assert _fired("p2p.conn.disconnect") == fired0 + 1
        finally:
            await _teardown(tracker, origin, agents, cluster)

    asyncio.run(main())


# -- scenario 5: at-rest bit flip -> scrub -> quarantine -> ring heal --------


def test_at_rest_bitflip_scrub_quarantine_heal_reconverges(tmp_path):
    """The full self-healing storage loop, end to end over real TCP: an
    injected at-rest bit flip (store.scrub.bitflip writes real damage to
    the platter) is detected by the scrubber, the blob is quarantined
    (file present under quarantine/, scrub_corruptions_total moves),
    restored bit-identical from the healthy ring replica through the
    persistedretry heal plane, and replication is re-enqueued so the
    ring converges back to max_replica."""

    async def main():
        from kraken_tpu.store.scrub import ScrubConfig

        origins = []
        for i in range(2):
            o = OriginNode(
                store_root=str(tmp_path / f"origin{i}"),
                piece_lengths=SMALL_PIECES,
                dedup=False,
                scrub=ScrubConfig(
                    interval_seconds=3600.0, bytes_per_second=0
                ),
            )
            await o.start()
            origins.append(o)
        ring = Ring(HostList(static=[o.addr for o in origins]), max_replica=2)
        for o in origins:
            o.ring = ring
            o.self_addr = o.addr
            o.server.ring = ring
            o.server.self_addr = o.addr
        try:
            blob = os.urandom(4 * 64 * 1024 + 77)
            d = Digest.from_bytes(blob)
            oc = BlobClient(origins[0].addr)
            await oc.upload(NS, d, blob)
            await oc.close()
            # The replication plane fills the second owner, then drains
            # fully: origin1's own replicate-back task must retire BEFORE
            # the corruption, or its push could race (and win against)
            # the heal pull this scenario is proving.
            await _wait_for(
                lambda: origins[1].store.in_cache(d),
                msg="initial replication to the second origin",
            )
            await _wait_for(
                lambda: not any(
                    o.retry.store.all_pending() for o in origins
                ),
                msg="replication plane quiescent",
            )

            corr0 = REGISTRY.counter("scrub_corruptions_total").value(
                source="scrub"
            )
            heal0 = REGISTRY.counter("blob_heals_total").value(source="ring")
            repl0 = REGISTRY.counter("replication_enqueued_total").value()

            failpoints.FAILPOINTS.arm("store.scrub.bitflip", "once")
            bad = await origins[0].scrubber.run_cycle()
            assert [b.hex for b in bad] == [d.hex]
            # Quarantined for post-mortem: damaged bytes present under
            # quarantine/, gone from the cache tree, counted.
            qpath = origins[0].store.quarantine_path(d)
            assert os.path.exists(qpath)
            with await asyncio.to_thread(open, qpath, "rb") as f:
                captured = await asyncio.to_thread(f.read)
            assert captured != blob and len(captured) == len(blob)
            assert not origins[0].store.in_cache(d)
            assert REGISTRY.counter("scrub_corruptions_total").value(
                source="scrub"
            ) == corr0 + 1

            # Heal: the retry plane re-fetches from the healthy replica,
            # bit-identity enforced by the verifying commit.
            await _wait_for(
                lambda: origins[0].store.in_cache(d),
                timeout=30.0,
                msg="heal re-fetch from the ring replica",
            )
            assert origins[0].store.read_cache_file(d) == blob
            # The heal metric and the re-enqueued replication land a
            # beat after the commit (post-commit pipeline): wait, don't
            # assert instantaneously.
            await _wait_for(
                lambda: REGISTRY.counter("blob_heals_total").value(
                    source="ring"
                ) == heal0 + 1,
                msg="heal counted against the ring source",
            )
            await _wait_for(
                lambda: REGISTRY.counter(
                    "replication_enqueued_total"
                ).value() > repl0,
                msg="replication re-enqueued after heal",
            )
            # And the healed blob still serves bit-identical over HTTP.
            oc2 = BlobClient(origins[0].addr)
            assert await oc2.download(NS, d) == blob
            await oc2.close()
        finally:
            for o in origins:
                await o.stop()

    asyncio.run(main())


# -- scenario 6: brown-out origin -> hedged reads keep pull latency bounded --


def test_brownout_origin_hedged_reads_keep_pull_latency_bounded(tmp_path):
    """The tail-tolerance acceptance gate (round 8): a SLOW-BUT-ALIVE
    origin (rpc.brownout.slow@addr stalls its read handlers 2 s, armed
    on one origin of two) must cost tail latency, not availability --
    with hedging on the tracker's metainfo path, p99 pull time stays
    within 2x the healthy baseline instead of eating the full 2 s stall
    on every pull whose primary replica is the browned-out origin."""

    async def main():
        from kraken_tpu.placement.healthcheck import PassiveFilter

        tracker = TrackerNode(
            announce_interval_seconds=0.1, peer_ttl_seconds=5.0
        )
        await tracker.start()
        origins = []
        for i in range(2):
            o = OriginNode(
                store_root=str(tmp_path / f"origin{i}"),
                tracker_addr=tracker.addr,
                piece_lengths=SMALL_PIECES,
                dedup=False,
            )
            await o.start()
            origins.append(o)
        ring = Ring(
            HostList(static=[o.addr for o in origins]), max_replica=2
        )
        cluster = ClusterClient(
            ring,
            health=PassiveFilter(name="chaos-brownout-breaker"),
            hedge_delay_seconds=0.15,
            deadline_seconds=10.0,
            component="tracker",
        )
        tracker.server.origin_cluster = cluster
        agent = AgentNode(
            store_root=str(tmp_path / "agent"), tracker_addr=tracker.addr
        )
        await agent.start()

        def blobs_with_slow_primary(n, salt):
            """Blobs whose ring PRIMARY is origins[0] -- the pulls that
            would eat the brown-out without hedging."""
            out = []
            i = 0
            while len(out) < n:
                blob = os.urandom(3 * 64 * 1024 + 11) + f"{salt}-{i}".encode()
                d = Digest.from_bytes(blob)
                if ring.locations(d)[0] == origins[0].addr:
                    out.append((d, blob))
                i += 1
            return out

        async def seed_everywhere(pairs):
            # Both origins hold + seed every blob, so the hedge target
            # can actually serve the metainfo and the swarm has a
            # healthy seeder either way.
            for o in origins:
                oc = BlobClient(o.addr)
                for d, blob in pairs:
                    await oc.upload(NS, d, blob)
                await oc.close()

        async def timed_pulls(pairs):
            walls = []
            for d, blob in pairs:
                t0 = asyncio.get_running_loop().time()
                assert await _pull(agent, d) == blob
                walls.append(asyncio.get_running_loop().time() - t0)
            return walls

        try:
            healthy_pairs = blobs_with_slow_primary(3, "healthy")
            brown_pairs = blobs_with_slow_primary(3, "brown")
            await seed_everywhere(healthy_pairs + brown_pairs)

            healthy = await timed_pulls(healthy_pairs)
            healthy_p99 = max(healthy)

            wins = REGISTRY.counter("rpc_hedge_wins_total")
            w0 = wins.value(op="get_metainfo")
            site = f"rpc.brownout.slow@{origins[0].addr}"
            failpoints.FAILPOINTS.arm(site, "always+delay:2000")
            brown = await timed_pulls(brown_pairs)
            brown_p99 = max(brown)

            assert _fired(site) >= 1  # the brown-out really stalled reads
            # The acceptance bound: within 2x the healthy baseline (the
            # +0.2 s floor keeps a sub-100ms baseline from turning timer
            # jitter into a false failure; the 2 s stall dwarfs both).
            assert brown_p99 <= 2 * healthy_p99 + 0.2, (
                f"brown-out stalled the pull: {brown} vs healthy {healthy}"
            )
            # The added cost must be hedge_delay-ish, never the 2 s
            # stall itself (relative bound: robust to a slow CI rig).
            assert brown_p99 - healthy_p99 < 1.0, (
                "pull ate the brown-out stall -- hedge never won"
            )
            assert wins.value(op="get_metainfo") > w0
        finally:
            failpoints.FAILPOINTS.disarm_all()
            await agent.stop()
            for o in origins:
                await o.stop()
            await cluster.close()
            await tracker.stop()

    asyncio.run(main())


# -- scenario 7: lameduck drain under an active swarm -> zero failed pulls ---


def test_drain_under_active_swarm_zero_failed_transfers(tmp_path):
    """SIGTERM's drain path, mid-transfer: the origin enters lameduck
    while a bandwidth-throttled pull is in flight. The established conn
    must finish every piece (bit-identity), new work must bounce with
    503+Retry-After, and the drain must quiesce on its own -- zero
    failed piece transfers, zero peer bans."""

    async def main():
        from kraken_tpu.p2p.scheduler import SchedulerConfig

        tracker = TrackerNode(
            announce_interval_seconds=0.1, peer_ttl_seconds=5.0
        )
        await tracker.start()
        origin = OriginNode(
            store_root=str(tmp_path / "origin"),
            tracker_addr=tracker.addr,
            piece_lengths=SMALL_PIECES,
            dedup=False,
            # Throttle egress so the pull is reliably still in flight
            # when the drain lands: the bucket's burst covers the first
            # corked batch (~1 MiB), then the remaining ~3 MiB pace out
            # at 1 MiB/s ~= 3 s of mid-drain transfer.
            p2p_bandwidth={"egress_bps": 1024 * 1024},
            # Short churn so the drained conn closes soon after the
            # transfer completes and drain() can quiesce.
            scheduler_config_doc={"conn_churn_idle_seconds": 1.0},
        )
        await origin.start()
        drain_cluster = ClusterClient(
            Ring(HostList(static=[origin.addr]), max_replica=1)
        )
        tracker.server.origin_cluster = drain_cluster
        agent = AgentNode(
            store_root=str(tmp_path / "agent"),
            tracker_addr=tracker.addr,
            scheduler_config=SchedulerConfig(announce_interval_seconds=0.1),
        )
        await agent.start()
        try:
            blob = os.urandom(64 * 64 * 1024 + 99)  # 65 pieces ~= 4 MiB
            d = Digest.from_bytes(blob)
            oc = BlobClient(origin.addr)
            await oc.upload(NS, d, blob)
            await oc.close()

            pull = asyncio.create_task(_pull(agent, d, timeout=60.0))
            # Wait until the transfer is genuinely in flight.
            await _wait_for(
                lambda: agent.scheduler.num_active_conns > 0
                and not pull.done(),
                msg="pull to open its p2p conn",
            )

            t0 = asyncio.get_running_loop().time()
            drain = asyncio.create_task(origin.drain(timeout=25.0))
            # While draining: health fails, new uploads bounce politely.
            import aiohttp

            async with aiohttp.ClientSession() as sess:
                async with sess.get(
                    f"http://{origin.addr}/health"
                ) as r:
                    assert r.status == 503
                async with sess.post(
                    f"http://{origin.addr}/namespace/{NS}/blobs/"
                    f"{Digest.from_bytes(b'new-upload').hex}/uploads"
                ) as r:
                    assert r.status == 503
                    assert r.headers.get("Retry-After")

            # The in-flight pull finishes bit-identical THROUGH the
            # drain: zero failed piece transfers.
            assert await asyncio.wait_for(pull, 45.0) == blob
            await asyncio.wait_for(drain, 30.0)
            drain_wall = asyncio.get_running_loop().time() - t0
            assert drain_wall < 24.0, "drain only ended at its timeout"
            # Nothing was banned and nothing misbehaved on either side.
            assert not agent.scheduler.conn_state.blacklist._entries
            # Conn teardown lands a callback-beat after the pull
            # resolves (more under KT_SANITIZE's asyncio debug mode):
            # the drain contract is that conns REACH zero, not that
            # they are zero at this exact instant.
            await _wait_for(
                lambda: agent.scheduler.num_active_conns == 0,
                timeout=5.0, msg="agent conns to reap after drain",
            )
        finally:
            await agent.stop()
            await origin.stop()
            await drain_cluster.close()
            await tracker.stop()

    asyncio.run(main())


# -- soak: probabilistic multi-fault swarm (slow) ----------------------------


@pytest.mark.slow
def test_chaos_soak_probabilistic_faults_swarm(tmp_path):
    """Seeded probabilistic corruption + disconnects + tracker errors,
    all at once, over a 3-agent swarm pulling several blobs: every pull
    must complete bit-identical. Fixed seeds make a failure replayable
    with KRAKEN_FAILPOINTS set to the same specs."""

    async def main():
        from kraken_tpu.p2p.connstate import ConnStateConfig
        from kraken_tpu.p2p.scheduler import SchedulerConfig

        # Quick-recovery blacklist: with probabilistic corruption an
        # agent may ban every seeder; the test asserts recovery, not
        # 30 s production cool-offs.
        cfg = SchedulerConfig(
            announce_interval_seconds=0.1,
            conn_state=ConnStateConfig(
                blacklist_backoff=Backoff(
                    base_seconds=0.3, factor=1.5, max_seconds=2.0, jitter=0
                ),
                soft_blacklist_seconds=0.3,
            ),
        )
        tracker, origin, agents, cluster = await _herd(
            tmp_path, n_agents=3, scheduler_config=cfg
        )
        try:
            blobs = []
            oc = BlobClient(origin.addr)
            for i in range(4):
                blob = os.urandom(4 * 64 * 1024 + i * 1111)
                blobs.append((Digest.from_bytes(blob), blob))
                await oc.upload(NS, blobs[-1][0], blob)
            await oc.close()

            failpoints.FAILPOINTS.arm(
                "p2p.conn.recv.corrupt", "prob:0.03+seed:1"
            )
            failpoints.FAILPOINTS.arm(
                "p2p.conn.disconnect", "prob:0.01+seed:2"
            )
            failpoints.FAILPOINTS.arm(
                "tracker.announce.error", "prob:0.2+seed:3"
            )
            failpoints.FAILPOINTS.arm(
                "p2p.conn.send.delay", "prob:0.05+delay:20+seed:4"
            )
            results = await asyncio.gather(
                *(
                    _pull(a, d, timeout=120.0)
                    for a in agents
                    for d, _b in blobs
                )
            )
            expected = [b for _a in agents for _d, b in blobs]
            assert results == expected  # bit-identity on EVERY pull
        finally:
            failpoints.FAILPOINTS.disarm_all()
            await _teardown(tracker, origin, agents, cluster)

    asyncio.run(main())


# -- scenario 8: origin SIGKILL mid-upload -> journaled resume, bit-identical -


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_origin_crash_mid_upload_client_resumes_bit_identical(tmp_path):
    """ACCEPTANCE: an origin hard-killed mid-upload (no clean-shutdown
    stamp, every in-memory tracker lost) restarts, fsck preserves the
    journaled session, HEAD re-adopts it at the durable offset, the
    client re-PATCHes ONLY the tail, and the committed digest + served
    metainfo are bit-identical to the single-shot oracle."""

    async def main():
        import aiohttp

        from kraken_tpu.core.hasher import get_hasher
        from kraken_tpu.origin.metainfogen import TorrentMetaMetadata

        piece = 64 * 1024
        blob = os.urandom(5 * piece + 77)
        d = Digest.from_bytes(blob)
        cut = 3 * piece + 11  # past the flush -> journaled durable offset
        port = _free_port()
        root = str(tmp_path / "origin")

        origin1 = OriginNode(
            store_root=root, http_port=port,
            piece_lengths=SMALL_PIECES, dedup=False,
        )
        await origin1.start()
        base = f"http://{origin1.addr}/namespace/{NS}/blobs/{d}"
        async with aiohttp.ClientSession() as http:
            async with http.post(f"{base}/uploads") as r:
                uid = await r.text()
            async with http.patch(
                f"{base}/uploads/{uid}", data=blob[:cut],
                headers={"X-Upload-Offset": "0"},
            ) as r:
                assert r.status == 204
        # SIGKILL stand-in: stop WITHOUT the clean-shutdown stamp. The
        # process state (upload trackers, pipeline sessions) dies with
        # it; only the spool + session journal survive on disk.
        mp = pytest.MonkeyPatch()
        mp.setattr(
            "kraken_tpu.assembly.write_clean_shutdown", lambda store: None
        )
        try:
            await origin1.stop()
        finally:
            mp.undo()

        origin2 = OriginNode(
            store_root=root, http_port=port,
            piece_lengths=SMALL_PIECES, dedup=False,
        )
        adopted0 = REGISTRY.counter("upload_sessions_adopted_total").value()
        await origin2.start()  # startup fsck preserves the live session
        try:
            async with aiohttp.ClientSession() as http:
                async with http.request(
                    "HEAD", f"{base}/uploads/{uid}"
                ) as r:
                    assert r.status == 200
                    offset = int(r.headers["X-Upload-Offset"])
                # Resume from the journaled durable offset: the client
                # re-sends ONLY the tail, not the whole blob.
                assert offset == cut
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[offset:],
                    headers={"X-Upload-Offset": str(offset)},
                ) as r:
                    assert r.status == 204
                async with http.put(f"{base}/uploads/{uid}/commit") as r:
                    assert r.status == 201
            assert (
                REGISTRY.counter("upload_sessions_adopted_total").value()
                == adopted0 + 1
            )
            assert origin2.store.read_cache_file(d) == blob
            stored = origin2.store.get_metadata(d, TorrentMetaMetadata)
            oracle = get_hasher("cpu").hash_pieces(blob, piece).tobytes()
            assert stored.metainfo.piece_hashes == oracle
            assert stored.metainfo.length == len(blob)
        finally:
            await origin2.stop()

    asyncio.run(main())


# -- scenario 9: device hasher dies mid-stream -> host fallback, identical ---


def test_device_hasher_failpoint_falls_back_host_bit_identical(tmp_path):
    async def main():
        from kraken_tpu.core.hasher import get_hasher
        from kraken_tpu.origin.metainfogen import TorrentMetaMetadata

        piece = 64 * 1024
        origin = OriginNode(
            store_root=str(tmp_path / "origin"),
            piece_lengths=SMALL_PIECES, dedup=False,
            ingest={"window_bytes": 1 << 20, "windows_in_flight": 2},
        )
        await origin.start()
        oc = BlobClient(origin.addr, HTTPClient(retries=0))
        try:
            blob = os.urandom(4 * piece + 123)
            d = Digest.from_bytes(blob)
            fell0 = REGISTRY.counter("ingest_fallbacks_total").value(
                reason="failpoint"
            )
            failpoints.FAILPOINTS.arm("origin.ingest.device_fail", "once")
            await oc.upload(NS, d, blob)  # degrades live, never errors
            assert _fired("origin.ingest.device_fail") >= 1
            assert (
                REGISTRY.counter("ingest_fallbacks_total").value(
                    reason="failpoint"
                )
                == fell0 + 1
            )
            stored = origin.store.get_metadata(d, TorrentMetaMetadata)
            oracle = get_hasher("cpu").hash_pieces(blob, piece).tobytes()
            assert stored.metainfo.piece_hashes == oracle
            assert await oc.download(NS, d) == blob
        finally:
            await oc.close()
            await origin.stop()

    asyncio.run(main())


# -- scenario 10: ENOSPC mid-PATCH -> the resuming client heals silently -----


def test_enospc_mid_patch_resume_heals_transparently(tmp_path):
    """The default (resume=True) client turns scenario 2's hard failure
    into a non-event: the failed PATCH is retried from the origin's
    durable offset under backoff and the upload completes with NO
    exception surfacing to the caller."""

    async def main():
        origin = OriginNode(
            store_root=str(tmp_path / "origin"),
            piece_lengths=SMALL_PIECES, dedup=False,
        )
        await origin.start()
        oc = BlobClient(origin.addr, HTTPClient(retries=0))
        try:
            blob = os.urandom(3 * 64 * 1024 + 500)
            d = Digest.from_bytes(blob)
            failpoints.FAILPOINTS.arm("origin.patch.write", "once")
            await oc.upload(NS, d, blob)  # no pytest.raises: it heals
            assert _fired("origin.patch.write") >= 1
            assert await oc.download(NS, d) == blob
        finally:
            await oc.close()
            await origin.stop()

    asyncio.run(main())


# -- scenario 11: agent pulls a blob whose commit hasn't finished ------------


def test_pull_of_still_ingesting_blob_serves_before_commit(tmp_path):
    """serve_while_ingest: once every byte is spooled and every piece
    hash known (commit REQUEST time), the metainfo publishes and the
    origin seeds straight from the spool -- an agent pull completes
    while the commit itself is still grinding (origin.commit.slow)."""

    async def main():
        from kraken_tpu.origin.metainfogen import TorrentMetaMetadata

        tracker = TrackerNode(
            announce_interval_seconds=0.1, peer_ttl_seconds=5.0
        )
        await tracker.start()
        origin = OriginNode(
            store_root=str(tmp_path / "origin"),
            tracker_addr=tracker.addr,
            piece_lengths=SMALL_PIECES,
            dedup=False,
            ingest={
                "window_bytes": 1 << 20,
                "windows_in_flight": 2,
                "serve_while_ingest": True,
            },
        )
        await origin.start()
        cluster = ClusterClient(
            Ring(HostList(static=[origin.addr]), max_replica=1)
        )
        tracker.server.origin_cluster = cluster
        agent = AgentNode(
            store_root=str(tmp_path / "agent"), tracker_addr=tracker.addr
        )
        await agent.start()
        oc = BlobClient(origin.addr, HTTPClient(retries=0))
        try:
            blob = os.urandom(5 * 64 * 1024 + 99)
            d = Digest.from_bytes(blob)
            # The commit stalls 3s AFTER early publish -- the window in
            # which the swarm must already be serving the spool bytes.
            failpoints.FAILPOINTS.arm("origin.commit.slow", "once+delay:3000")
            upload_task = asyncio.create_task(oc.upload(NS, d, blob))
            # Early publish lands the metainfo sidecar before commit.
            await _wait_for(
                lambda: origin.store.get_metadata(d, TorrentMetaMetadata)
                is not None,
                msg="early-published metainfo",
            )
            got = await _pull(agent, d)
            assert not upload_task.done(), (
                "pull must complete INSIDE the commit window"
            )
            assert got == blob
            await upload_task  # the slow commit still succeeds
            assert origin.store.in_cache(d)
            assert await _pull(agent, d) == blob  # post-promote re-serve
        finally:
            await oc.close()
            await agent.stop()
            await origin.stop()
            await cluster.close()
            await tracker.stop()

    asyncio.run(main())


# -- scenario: link-fault matrix at the HTTP transport -----------------------


def test_link_fault_matrix_partitions_by_destination():
    """`rpc.link.drop@{dst}` severs every HTTP request INTO one host
    while other destinations stay reachable -- the primitive partition
    tests are built from. Global `rpc.link.drop` kills all destinations;
    `rpc.link.delay@{dst}` injects latency without severing."""

    async def main():
        import time

        from aiohttp import web

        async def ok(request):
            return web.Response(body=b"ok")

        runners, bases, dsts = [], [], []
        for _ in range(2):
            app = web.Application()
            app.router.add_get("/x", ok)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            port = runner.addresses[0][1]
            runners.append(runner)
            bases.append(f"http://127.0.0.1:{port}")
            dsts.append(f"127.0.0.1:{port}")

        http = HTTPClient(retries=0)
        try:
            # Destination-selective: only dsts[0] is partitioned.
            failpoints.FAILPOINTS.arm(f"rpc.link.drop@{dsts[0]}", "always")
            import aiohttp

            with pytest.raises(aiohttp.ClientConnectionError):
                await http.get(f"{bases[0]}/x")
            assert await http.get(f"{bases[1]}/x") == b"ok"
            assert _fired(f"rpc.link.drop@{dsts[0]}") >= 1
            failpoints.FAILPOINTS.disarm_all()

            # Global variant: EVERY destination is dark.
            failpoints.FAILPOINTS.arm("rpc.link.drop", "always")
            for base in bases:
                with pytest.raises(aiohttp.ClientConnectionError):
                    await http.get(f"{base}/x")
            assert _fired("rpc.link.drop") >= 2
            failpoints.FAILPOINTS.disarm_all()

            # Delay variant: slow link, not a severed one.
            failpoints.FAILPOINTS.arm(
                f"rpc.link.delay@{dsts[1]}", "always+delay:80"
            )
            t0 = time.monotonic()
            assert await http.get(f"{bases[1]}/x") == b"ok"
            assert time.monotonic() - t0 >= 0.08
        finally:
            await http.close()
            for runner in runners:
                await runner.cleanup()

    asyncio.run(main())


# -- scenario: crash between hint replay and task retirement -----------------


def test_hint_replay_crash_window_is_effectively_once(tmp_path):
    """`origin.hint.replay.crash` fires AFTER the replay push lands but
    BEFORE the task retires: the hint must stay journaled, and the re-run
    must converge as a cheap stat hit (effectively-once), retiring the
    task and counting exactly one replay."""

    async def main():
        import socket
        import time

        from kraken_tpu.origin.server import HINT_KIND, _hint_task

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        ports = [free_port() for _ in range(2)]
        addrs = [f"127.0.0.1:{p}" for p in ports]
        nodes = []
        for i in range(2):
            node = OriginNode(
                store_root=str(tmp_path / f"origin{i}"),
                http_port=ports[i],
                ring=Ring(HostList(static=addrs), max_replica=2),
                self_addr=addrs[i],
                dedup=False,
            )
            await node.start()
            node.retry.stop()  # tests drive run_once by hand
            nodes.append(node)
        try:
            blob = os.urandom(100_000)
            d = Digest.from_bytes(blob)
            oc = BlobClient(addrs[0])
            await oc.upload(NS, d, blob)
            await oc.close()
            assert not nodes[1].store.in_cache(d)

            # Journal a hint for the replica by hand (as a partition at
            # commit would) and crash the first replay attempt.
            nodes[0].retry.add(
                _hint_task(addrs[1], NS, d, time.time() + 3600.0)
            )
            replayed0 = REGISTRY.counter("origin_hints_total").value(
                state="replayed"
            )
            failpoints.FAILPOINTS.arm("origin.hint.replay.crash", "once")
            await nodes[0].retry.run_once()
            assert _fired("origin.hint.replay.crash") >= 1
            # The push landed, but the crash kept the task journaled
            # and the replay uncounted.
            assert nodes[1].store.in_cache(d)
            assert (
                nodes[0].retry.store.count_pending(HINT_KIND, f"{d.hex}:")
                == 1
            )
            assert (
                REGISTRY.counter("origin_hints_total").value(state="replayed")
                == replayed0
            )

            # Re-run past the failure backoff: stat-first replay retires
            # the task; exactly ONE replay is counted for the pair.
            await nodes[0].retry.run_once(now=time.time() + 3600.0)
            assert (
                nodes[0].retry.store.count_pending(HINT_KIND, f"{d.hex}:")
                == 0
            )
            assert (
                REGISTRY.counter("origin_hints_total").value(state="replayed")
                == replayed0 + 1
            )
            c = BlobClient(addrs[1])
            assert await c.download(NS, d) == blob
            await c.close()
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(main())
