"""Single-pair data-plane benchmark: 1 origin seeder -> 1 agent leecher
over loopback TCP, one process.

VERDICT r4 next-round #1: the swarm bench proved the *policies* scale; this
measures (and profiles) what one conn pair can MOVE -- the harness ceiling
every aggregate number divides into. Run with --profile to get a cProfile
table of the combined event loop (both endpoints + both pumps), which is
what localized the round-5 rebuild targets (per-piece file opens, per-piece
bitfield sidecar writes, 64 KiB StreamReader chunking, frame-copy framing).

Round 7 adds two honesty instruments:

- ``pump_ceiling_mbps``: the all-knockout row (verify + data write
  no-op'd) -- what the pure pump + dispatch machinery moves. This is the
  number the zero-copy wire plane targets; the full-stack number on this
  one-core rig stays verify-bound.
- ``recv_alloc_per_piece``: a tracemalloc sample of bytes allocated in
  the wire/conn/dispatch layers per received piece. The round-5 path
  paid ~2x payload per piece (readexactly + the ``raw[header_len:]``
  slice); the pooled path must hold this near zero or the zero-copy
  claim is marketing.

Usage:
    python bench_pair.py [--blob-mb 256] [--piece-kb 1024] [--profile]
                         [--repeats 3] [--skip-knockout] [--skip-alloc]

Prints one JSON line per metric; {"metric": "pair_goodput_mbps", ...}
stays the headline row.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import cProfile
import io
import json
import os
import pstats
import statistics
import tempfile
import time
import tracemalloc

import numpy as np

from bench_swarm import InMemoryTracker, make_peer, NS
from kraken_tpu.core.digest import Digest
from kraken_tpu.core.hasher import get_hasher
from kraken_tpu.core.metainfo import MetaInfo


async def run_pair(blob_mb: int, piece_kb: int, root: str,
                   workers: int = 0, leech_workers: int = 0,
                   reset_profiler: bool = False) -> dict:
    rng = np.random.default_rng(0)
    blob = rng.integers(0, 256, size=blob_mb << 20, dtype=np.uint8).tobytes()
    d = Digest.from_bytes(blob)
    piece_len = piece_kb << 10
    hashes = get_hasher("cpu").hash_pieces(blob, piece_len)
    metainfo = MetaInfo(d, len(blob), piece_len, hashes.tobytes())

    tracker = InMemoryTracker()
    tracker.metainfos[d.hex] = metainfo
    origin = make_peer(root, "origin", tracker, seed_blobs=[blob],
                       data_plane_workers=workers)
    agent = make_peer(root, "agent", tracker, leech_workers=leech_workers)
    await origin.start()
    origin.seed(metainfo, NS)
    await agent.start()

    if reset_profiler:
        # Attribution runs scope the sampler to the DOWNLOAD: blob
        # generation, metainfo hashing, and store fill above are bench
        # setup, not pull cost.
        from kraken_tpu.utils.profiler import PROFILER

        PROFILER.reset()
    # CPU accounting window: download through the stops below, so worker
    # children are reaped (os.times only credits children after waitpid)
    # and the seed-serve CPU rows can split main-loop vs shard cost.
    cpu0 = os.times()
    t0 = time.perf_counter()
    await agent.download(NS, d)
    wall = time.perf_counter() - t0

    # Leak accounting must wait out the in-flight tail: the completing
    # piece's task resolves download() BEFORE its own done-callback
    # returns the last lease, so an immediate read would cry wolf. A
    # true leak never drains and still reports after the grace loop.
    pool = agent._bufpool  # leases = received payload frames
    for _ in range(100):
        if pool.leased == 0:
            break
        await asyncio.sleep(0.01)
    pool_stats = {
        "bufpool_allocated": pool.allocated,
        "bufpool_leases": pool.hits + pool.misses,
        "bufpool_hit_ratio": round(pool.hit_ratio(), 4),
        "bufpool_leaked": pool.leased,  # non-zero = a lease never returned
    }
    await origin.stop()
    await agent.stop()
    cpu1 = os.times()
    return {
        "blob_mb": blob_mb,
        "piece_kb": piece_kb,
        "pieces": metainfo.num_pieces,
        "workers": workers,
        "leech_workers": leech_workers,
        "wall_s": round(wall, 4),
        "goodput_mbps": round(len(blob) / wall / 1e6, 1),
        # Main-process CPU (both endpoints' loops + verify threads) and
        # reaped-children CPU (the worker shards' serve cost) over the
        # download window -- the seed_cpu_per_byte row's raw inputs.
        "cpu_main_s": round(
            (cpu1.user - cpu0.user) + (cpu1.system - cpu0.system), 4
        ),
        "cpu_children_s": round(
            (cpu1.children_user - cpu0.children_user)
            + (cpu1.children_system - cpu0.children_system), 4
        ),
        **pool_stats,
    }


@contextlib.contextmanager
def knockout_endpoints():
    """No-op the endpoint machinery (verify hash + piece data write) so a
    run measures the pure pump + dispatch cost -- the same knockout
    tests/test_data_plane_band.py ratio-gates in CI. Bitfield sidecar IO
    is already debounced to ~0 and stays live."""
    from kraken_tpu.p2p import storage as st

    async def _verified(self, data, expected, times=None):
        return True

    orig_verify = st.BatchedVerifier.verify
    orig_write = st.Torrent._write_at
    st.BatchedVerifier.verify = _verified
    st.Torrent._write_at = lambda self, i, data: None
    try:
        yield
    finally:
        st.BatchedVerifier.verify = orig_verify
        st.Torrent._write_at = orig_write


# The files a recv-path payload allocation is attributed to: the frame
# plane itself (the round-5 slice copy lived here) and the pool (a miss
# allocates here -- reuse failure; also pinned via pool_allocated below).
# asyncio/streams.py is deliberately NOT filtered: the offline harness
# pre-feeds all frames, and the reader's internal-buffer compaction gets
# attributed there at payload scale -- harness artifact, not wire cost.
# The readexactly-into-view fallback (transient, freed before any
# snapshot could see it) is instead guarded by the hasattr probe in
# _readinto_exactly plus the real-transport pool pins in
# tests/test_wire_plane.py::test_loopback_pull_reuses_buffers.
_WIRE_FILES = ("p2p/wire.py", "utils/bufpool.py")


def run_alloc_sample(pieces: int = 16, piece_kb: int = 256) -> dict:
    """Deterministic per-piece allocation count on the recv framing path.

    Feeds ``pieces`` PIECE_PAYLOAD frames through ``recv_message`` with a
    warmed buffer pool and, WHILE HOLDING each decoded message (its
    payload still live -- transient copies can't hide from the snapshot),
    measures live bytes attributed to the wire files. The round-5 path
    charged a full payload per frame here (the ``raw[header_len:]``
    slice); the pooled path must charge ~none -- the payload lives in a
    recycled, already-counted bufpool buffer, not a fresh allocation.
    Shared with tests/test_wire_plane.py's regression pin, so the bench
    and the CI gate cannot drift apart.
    """
    from kraken_tpu.p2p.wire import Message, recv_message, send_messages
    from kraken_tpu.utils.bufpool import BufferPool

    piece_len = piece_kb << 10

    class _Sink:
        def __init__(self):
            self.buf = bytearray()

        def write(self, b):
            self.buf += b

        def writelines(self, bufs):
            for b in bufs:
                self.buf += b

        async def drain(self):
            pass

    async def sample() -> tuple[int, int, int]:
        pool = BufferPool()
        payload = os.urandom(piece_len)
        sink = _Sink()
        await send_messages(
            sink, [Message.piece_payload(i, payload) for i in range(pieces)]
        )
        # Warm the pool (first lease allocates; steady state must reuse).
        warm_sink = _Sink()
        await send_messages(warm_sink, [Message.piece_payload(0, payload)])
        warm = asyncio.StreamReader()
        warm.feed_data(bytes(warm_sink.buf))
        warm.feed_eof()
        (await recv_message(warm, pool=pool)).release()

        reader = asyncio.StreamReader()
        reader.feed_data(bytes(sink.buf))
        reader.feed_eof()
        tracemalloc.start(10)
        try:
            base = tracemalloc.take_snapshot()
            wire_bytes = 0
            wire_blocks = 0
            for _ in range(pieces):
                msg = await recv_message(reader, pool=pool)
                snap = tracemalloc.take_snapshot()
                for f in _WIRE_FILES:
                    stats = snap.filter_traces(
                        [tracemalloc.Filter(True, f"*{f}")]
                    ).compare_to(
                        base.filter_traces(
                            [tracemalloc.Filter(True, f"*{f}")]
                        ),
                        "filename",
                    )
                    wire_bytes += sum(max(0, s.size_diff) for s in stats)
                    wire_blocks += sum(max(0, s.count_diff) for s in stats)
                msg.release()
        finally:
            tracemalloc.stop()
        return wire_bytes, wire_blocks, pool.allocated

    total_bytes, total_blocks, pool_allocated = asyncio.run(sample())
    return {
        "metric": "recv_alloc_per_piece",
        "pieces": pieces,
        "piece_kb": piece_kb,
        "wire_bytes_per_piece": round(total_bytes / pieces, 1),
        "wire_blocks_per_piece": round(total_blocks / pieces, 2),
        "payload_fraction": round(total_bytes / pieces / piece_len, 4),
        # Post-warm this must stay at 1: every further frame reuses the
        # same recycled buffer (a climb = the pool stopped recycling).
        "pool_allocated": pool_allocated,
    }


def run_brownout(hedge_delay_s: float = 0.1, slow_s: float = 0.5,
                 reads: int = 40, blob_kb: int = 256) -> dict:
    """Brown-out row (round 8, overload & degradation plane): two origin
    read endpoints behind a hedged ClusterClient, with the ring PRIMARY
    stalling ``slow_s`` per request (slow-but-alive). Reports read p50/
    p99 and the hedge win rate -- the honesty number for the "a brown-out
    costs tail latency, not availability" claim. Without hedging every
    read would eat the full ``slow_s``; with it, p99 should sit near
    ``hedge_delay_s`` + healthy service time."""
    from aiohttp import web

    from kraken_tpu.origin.client import BlobClient, ClusterClient
    from kraken_tpu.placement import HostList, Ring
    from kraken_tpu.utils.httputil import HTTPClient
    from kraken_tpu.utils.metrics import REGISTRY

    body = os.urandom(blob_kb << 10)

    async def sample():
        async def make_server(delay: float):
            async def blob(req):
                if delay:
                    await asyncio.sleep(delay)
                return web.Response(body=body)

            app = web.Application()
            app.router.add_get("/namespace/{ns}/blobs/{d}", blob)
            runner = web.AppRunner(
                app, handler_cancellation=True, shutdown_timeout=0.1
            )
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            return runner, f"127.0.0.1:{runner.addresses[0][1]}"

        slow_runner, slow_addr = await make_server(slow_s)
        fast_runner, fast_addr = await make_server(0.0)
        ring = Ring(HostList(static=[slow_addr, fast_addr]), max_replica=2)
        cluster = ClusterClient(
            ring,
            client_factory=lambda a: BlobClient(a, HTTPClient(retries=0)),
            hedge_delay_seconds=hedge_delay_s,
            component="bench-brownout",
        )
        hedges = REGISTRY.counter("rpc_hedges_total")
        wins = REGISTRY.counter("rpc_hedge_wins_total")
        h0 = hedges.value(op="download")
        w0 = wins.value(op="download")
        lat = []
        try:
            i = 0
            done = 0
            while done < reads:
                from kraken_tpu.core.digest import Digest

                d = Digest.from_bytes(f"brownout-{i}".encode())
                i += 1
                if ring.locations(d)[0] != slow_addr:
                    continue  # only reads whose primary is browned out
                t0 = time.perf_counter()
                got = await cluster.download(NS_BROWNOUT, d)
                lat.append(time.perf_counter() - t0)
                assert got == body
                done += 1
        finally:
            await cluster.close()
            await slow_runner.cleanup()
            await fast_runner.cleanup()
        launched = hedges.value(op="download") - h0
        won = wins.value(op="download") - w0
        return lat, launched, won

    lat, launched, won = asyncio.run(sample())
    lat.sort()
    return {
        "metric": "brownout_hedge",
        "reads": reads,
        "slow_s": slow_s,
        "hedge_delay_s": hedge_delay_s,
        "p50_ms": round(lat[len(lat) // 2] * 1e3, 1),
        "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3, 1),
        "hedges_launched": launched,
        "hedge_win_rate": round(won / launched, 3) if launched else None,
    }


NS_BROWNOUT = "bench-brownout"


def _run_repeats(args, knockout: bool, workers: int = 0,
                 leech_workers: int = 0) -> list[dict]:
    results = []
    for _ in range(args.repeats):
        with tempfile.TemporaryDirectory() as root:
            if args.profile and not knockout:
                prof = cProfile.Profile()
                prof.enable()
            ctx = knockout_endpoints() if knockout else contextlib.nullcontext()
            with ctx:
                r = asyncio.run(
                    run_pair(args.blob_mb, args.piece_kb, root,
                             workers=workers, leech_workers=leech_workers)
                )
            if args.profile and not knockout:
                prof.disable()
                s = io.StringIO()
                pstats.Stats(prof, stream=s).sort_stats("cumulative").print_stats(40)
                print(s.getvalue())
            results.append(r)
            print(json.dumps({**r, "knockout": knockout}))
    return results


def run_workers_scaling(args) -> None:
    """Round 8 honesty row #1: pair goodput with the seed-serve plane on
    the main loop (workers=0, the PR-6 stack) vs sharded across 2 worker
    processes -- median±spread of ``--repeats`` runs each, same rig,
    same harness. On a pair the serve side is a small slice of the
    critical path (the leech half -- recv copies, verify, write -- binds
    it), so expect single-digit gains HERE; the serve-plane rows below
    are where the multi-core claim is measured."""

    def med(vals):
        return statistics.median(sorted(vals))

    r0 = _run_repeats(args, knockout=False, workers=0)
    r2 = _run_repeats(args, knockout=False, workers=2)
    g0 = sorted(r["goodput_mbps"] for r in r0)
    g2 = sorted(r["goodput_mbps"] for r in r2)
    print(json.dumps({
        "metric": "workers_scaling",
        "unit": "MB/s",
        "workers0_mbps": med(g0),
        "workers0_min": g0[0], "workers0_max": g0[-1],
        "workers2_mbps": med(g2),
        "workers2_min": g2[0], "workers2_max": g2[-1],
        "median_of": len(g0),
        "speedup": round(med(g2) / med(g0), 3) if med(g0) else None,
    }))


def run_leech_workers_scaling(args) -> None:
    """Round 19 headline row: pair goodput with the DOWNLOAD plane on
    the main loop (leech_workers=0) vs pumped through 2 leech worker
    processes (recv + frame parse + pwrite in forked shards, payloads
    via the shared ring, verify batched in the parent) --
    median±spread of ``--repeats`` runs each, same rig, same harness.
    The leech half IS the pair's critical path, so unlike the seed-side
    workers_scaling row this is where the multi-core download claim is
    measured: >= 1.3x on a >= 2-core rig is the acceptance bar
    (PERF.md "Leech shard plane"); on a 1-core rig expect ~1.0x -- the
    pump and the verifier time-slice one core."""

    def med(vals):
        return statistics.median(sorted(vals))

    r0 = _run_repeats(args, knockout=False, leech_workers=0)
    r2 = _run_repeats(args, knockout=False, leech_workers=2)
    g0 = sorted(r["goodput_mbps"] for r in r0)
    g2 = sorted(r["goodput_mbps"] for r in r2)
    print(json.dumps({
        "metric": "leech_workers_scaling",
        "unit": "MB/s",
        "cores": os.cpu_count(),
        "leech0_mbps": med(g0),
        "leech0_min": g0[0], "leech0_max": g0[-1],
        "leech2_mbps": med(g2),
        "leech2_min": g2[0], "leech2_max": g2[-1],
        "median_of": len(g0),
        "speedup": round(med(g2) / med(g0), 3) if med(g0) else None,
    }))


# -- the serve-isolated harness (seed_cpu_per_byte) ------------------------

_LEECH_PIPELINE = 16


def _leech_proc(port: int, ih_hex: str, name_hex: str, num_pieces: int,
                piece_len: int, rounds: int, q) -> None:
    """Raw leecher subprocess: handshake, pipeline PIECE_REQUESTs,
    read-and-discard payloads. Runs OUTSIDE the origin's process so the
    origin's os.times() isolates serve-side cost; reports its own bytes,
    wall, and CPU (subtracted from the parent's children-CPU so shard
    CPU can be attributed exactly)."""
    import socket as socket_mod

    import msgpack

    s = socket_mod.create_connection(("127.0.0.1", port))
    f = s.makefile("rwb")

    def send_msg(t: int, header: dict, payload: bytes = b"") -> None:
        h = msgpack.packb(header)
        f.write(
            bytes([t]) + len(h).to_bytes(4, "big")
            + len(payload).to_bytes(4, "big") + h + payload
        )

    def read_frame():
        pre = f.read(9)
        if len(pre) < 9:
            raise ConnectionResetError("seeder closed")
        hl = int.from_bytes(pre[1:5], "big")
        pl = int.from_bytes(pre[5:9], "big")
        if hl:
            f.read(hl)
        left = pl
        while left:
            chunk = f.read(min(left, 1 << 20))
            if not chunk:
                raise ConnectionResetError("seeder closed mid-payload")
            left -= len(chunk)
        return pre[0], pl

    send_msg(0, {
        "peer_id": os.urandom(20).hex(), "info_hash": ih_hex,
        "name": name_hex, "namespace": "bench-serve",
        "num_pieces": num_pieces,
    }, bytes((num_pieces + 7) // 8))
    f.flush()
    read_frame()  # the seeder's handshake reply
    total = 0
    t0 = time.perf_counter()
    for _ in range(rounds):
        nxt = got = outstanding = 0
        while got < num_pieces:
            while outstanding < _LEECH_PIPELINE and nxt < num_pieces:
                send_msg(2, {"index": nxt})
                nxt += 1
                outstanding += 1
            f.flush()
            t, pl = read_frame()
            if t == 3:
                got += 1
                outstanding -= 1
                total += pl
    wall = time.perf_counter() - t0
    tm = os.times()
    q.put((total, wall, tm.user + tm.system))
    s.close()


async def _seed_serve_once(root: str, blob: bytes, metainfo,
                           workers: int, leechers: int,
                           rounds: int) -> dict:
    import multiprocessing

    from bench_swarm import make_peer

    tracker = InMemoryTracker(interval=30.0)
    tracker.metainfos[metainfo.digest.hex] = metainfo
    origin = make_peer(root, "origin", tracker, seed_blobs=[blob],
                       data_plane_workers=workers)
    await origin.start()
    origin.seed(metainfo, NS)
    ctx = multiprocessing.get_context("fork")
    q = ctx.Queue()
    cpu0 = os.times()
    t0 = time.perf_counter()
    procs = [
        ctx.Process(
            target=_leech_proc,
            args=(origin.port, metainfo.info_hash.hex, metainfo.digest.hex,
                  metainfo.num_pieces, metainfo.piece_length, rounds, q),
            daemon=True,
        )
        for _ in range(leechers)
    ]
    for p in procs:
        p.start()
    results = [await asyncio.to_thread(q.get) for _ in procs]
    for p in procs:
        await asyncio.to_thread(p.join)
    wall = time.perf_counter() - t0
    await origin.stop()  # reaps shards: their CPU lands in children
    cpu1 = os.times()
    total = sum(r[0] for r in results)
    leech_cpu = sum(r[2] for r in results)
    main = (cpu1.user - cpu0.user) + (cpu1.system - cpu0.system)
    children = (
        (cpu1.children_user - cpu0.children_user)
        + (cpu1.children_system - cpu0.children_system)
    )
    return {
        "bytes": total,
        "goodput_mbps": round(total / wall / 1e6, 1),
        "main_cpu_s": round(main, 3),
        "shard_cpu_s": round(max(0.0, children - leech_cpu), 3),
    }


def run_seed_serve(args, leechers: int = 2, rounds: int = 4) -> None:
    """Round 8 honesty rows #2-3: the serve plane ISOLATED -- the origin
    scheduler alone in this process, raw leecher subprocesses pulling
    every piece ``rounds`` times and discarding, so ``os.times`` splits
    the serve cost exactly:

    - ``seed_serve_goodput_mbps``: the origin's aggregate serve rate,
      workers=0 (every serve on the main loop) vs workers=2 (sendfile
      in shards);
    - ``seed_cpu_per_byte``: what serving one byte costs the origin's
      MAIN LOOP (the scarce resource -- it also runs ingest, hashing,
      breakers, announce) before vs after, plus the total including
      shard CPU (on kernels where sendfile is emulated the total moves
      little; the loop liberation is the durable win).
    """

    def med(vals):
        return statistics.median(sorted(vals))

    rng = np.random.default_rng(0)
    blob = rng.integers(
        0, 256, size=args.blob_mb << 20, dtype=np.uint8
    ).tobytes()
    d = Digest.from_bytes(blob)
    piece_len = args.piece_kb << 10
    hashes = get_hasher("cpu").hash_pieces(blob, piece_len)
    metainfo = MetaInfo(d, len(blob), piece_len, hashes.tobytes())

    rows: dict[int, list[dict]] = {0: [], 2: []}
    for workers in (0, 2):
        for _ in range(args.repeats):
            with tempfile.TemporaryDirectory() as root:
                r = asyncio.run(_seed_serve_once(
                    root, blob, metainfo, workers, leechers, rounds
                ))
                rows[workers].append(r)
                print(json.dumps({
                    "metric": "seed_serve_run", "workers": workers, **r
                }))
    g0 = sorted(r["goodput_mbps"] for r in rows[0])
    g2 = sorted(r["goodput_mbps"] for r in rows[2])
    print(json.dumps({
        "metric": "seed_serve_goodput_mbps",
        "unit": "MB/s",
        "leechers": leechers,
        "workers0_mbps": med(g0), "workers0_min": g0[0], "workers0_max": g0[-1],
        "workers2_mbps": med(g2), "workers2_min": g2[0], "workers2_max": g2[-1],
        "median_of": len(g0),
    }))
    nbytes = rows[0][0]["bytes"]
    loop_before = med([r["main_cpu_s"] for r in rows[0]]) / nbytes
    loop_after = med([r["main_cpu_s"] for r in rows[2]]) / nbytes
    total_before = loop_before  # workers=0: the loop IS the serve cost
    total_after = (
        med([r["main_cpu_s"] + r["shard_cpu_s"] for r in rows[2]]) / nbytes
    )
    print(json.dumps({
        "metric": "seed_cpu_per_byte",
        "unit": "ns/B",
        "loop_before_ns_per_byte": round(loop_before * 1e9, 3),
        "loop_after_ns_per_byte": round(loop_after * 1e9, 3),
        "loop_reduction_pct": (
            round(100 * (1 - loop_after / loop_before), 1)
            if loop_before > 0 else None
        ),
        "total_before_ns_per_byte": round(total_before * 1e9, 3),
        "total_after_ns_per_byte": round(total_after * 1e9, 3),
        "total_reduction_pct": (
            round(100 * (1 - total_after / total_before), 1)
            if total_before > 0 else None
        ),
    }))


def run_trace_overhead(args) -> None:
    """Round 9 honesty row: what the distributed-tracing plane costs the
    data path at the SHIPPED sampling rate (base.yaml
    ``trace.sample_rate``, 0.01). Two legs, each trace-off vs trace-on:
    the full stack and the pump knockout (the pure pump + dispatch
    machinery, where per-piece span gating would show first). Legs are
    run back-to-back on the same rig so the on/off ratio cancels the
    shared-core drift the absolute numbers ride. The CI version of this
    row is tests/test_data_plane_band.py::test_trace_on_overhead_band
    (ratio gated at <= 5% goodput cost)."""
    from kraken_tpu.configutil import load_config
    from kraken_tpu.utils.trace import TRACER, TraceConfig

    # The row's claim is "at the SHIPPED rate": read it from the actual
    # shipped config, not the dataclass default, so a base.yaml rate
    # change cannot silently turn this into a measurement of something
    # else (test_config_tree only pins the rate to a sampled-down RANGE).
    shipped = TraceConfig.from_dict(
        load_config(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "config", "agent", "base.yaml")
        ).get("trace")
    )

    def med(vals):
        return statistics.median(sorted(vals))

    def leg(enabled: bool, knockout: bool) -> list[float]:
        TRACER.apply(shipped if enabled else TraceConfig(enabled=False))
        try:
            return [
                r["goodput_mbps"]
                for r in _run_repeats(args, knockout=knockout)
            ]
        finally:
            TRACER.apply(TraceConfig())

    row: dict = {
        "metric": "trace_overhead",
        "unit": "MB/s",
        "sample_rate": shipped.sample_rate,
    }
    for label, knockout in (("full", False), ("pump", True)):
        if knockout and args.skip_knockout:
            continue
        off = leg(False, knockout)
        on = leg(True, knockout)
        row[f"{label}_off_mbps"] = med(off)
        row[f"{label}_on_mbps"] = med(on)
        row[f"{label}_on_off_ratio"] = (
            round(med(on) / med(off), 4) if med(off) else None
        )
    print(json.dumps(row))


def run_profiler_overhead(args) -> None:
    """Round 11 honesty row: what the always-on sampling profiler costs
    the data path at the SHIPPED rate (base.yaml ``profiling.hz``).
    Same protocol as the trace_overhead row: full-stack and pump-
    knockout legs, each profiler-off vs profiler-on back to back so the
    ratio cancels shared-core drift. The CI version is
    tests/test_data_plane_band.py::test_profiler_on_overhead_band."""
    from kraken_tpu.configutil import load_config
    from kraken_tpu.utils.profiler import PROFILER, ProfilerConfig

    shipped = ProfilerConfig.from_dict(
        load_config(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "config", "agent", "base.yaml")
        ).get("profiling")
    )

    def med(vals):
        return statistics.median(sorted(vals))

    def leg(enabled: bool, knockout: bool) -> list[float]:
        PROFILER.apply(
            shipped if enabled else ProfilerConfig(enabled=False)
        )
        try:
            return [
                r["goodput_mbps"]
                for r in _run_repeats(args, knockout=knockout)
            ]
        finally:
            PROFILER.apply(ProfilerConfig(enabled=False))
            PROFILER.reset()

    row: dict = {
        "metric": "profiler_overhead",
        "unit": "MB/s",
        "hz": shipped.hz,
    }
    for label, knockout in (("full", False), ("pump", True)):
        if knockout and args.skip_knockout:
            continue
        off = leg(False, knockout)
        on = leg(True, knockout)
        row[f"{label}_off_mbps"] = med(off)
        row[f"{label}_on_mbps"] = med(on)
        row[f"{label}_on_off_ratio"] = (
            round(med(on) / med(off), 4) if med(off) else None
        )
    print(json.dumps(row))


def run_leech_attribution(args, hz: float = 97.0,
                          flame_dir: str | None = None) -> dict:
    """THE headline artifact of the profiling plane: the measured
    leech-side attribution -- where a real pull's busy samples actually
    go (pump recv framing vs verify hashing vs pwrite vs dispatch) --
    from a pair run with ``data_plane_workers=2`` so the origin's serve
    cost sits in forked shards, sampled and shipped home like
    production. This is the number that decides ROADMAP item 3's next
    move (leech-side sharding vs a C framing helper). Sampled at a
    HIGHER hz than shipped (resolution, not cost, is the point of a
    one-off run); also writes a profile dump + `kraken-tpu flame`
    collapse covering main loop plus shards when ``flame_dir`` is
    given."""
    from kraken_tpu.utils.profiler import (
        PROFILER,
        ProfilerConfig,
        plane_pct_busy,
    )

    PROFILER.apply(ProfilerConfig(
        hz=hz, window_seconds=600.0, keep_windows=2,
        dump_dir=flame_dir or "",
    ))
    PROFILER.node = "pair"
    PROFILER.reset()
    try:
        with tempfile.TemporaryDirectory() as root:
            r = asyncio.run(run_pair(args.blob_mb, args.piece_kb, root,
                                     workers=2, reset_profiler=True))
        planes = PROFILER.plane_totals()
        dump_path = None
        if flame_dir:
            dump_path = PROFILER.dump("bench", "leech attribution run")
    finally:
        PROFILER.apply(ProfilerConfig(enabled=False))
    busy = sum(c for p, c in planes.items() if p != "idle")
    row = {
        "metric": "leech_attribution",
        "hz": hz,
        "workers": 2,
        "blob_mb": args.blob_mb,
        "wall_s": r["wall_s"],
        "goodput_mbps": r["goodput_mbps"],
        "samples_busy": busy,
        "samples_idle": planes.get("idle", 0),
        "plane_samples": {k: v for k, v in sorted(planes.items())},
        "plane_pct_busy": plane_pct_busy(planes),
        "flame_dump": dump_path,
    }
    print(json.dumps(row))
    return row


def _summarize(metric: str, results: list[dict]) -> None:
    # Median +/- spread of N runs (VERDICT r5 next #3): single best-of
    # runs on this shared core produced BENCH-vs-PERF discrepancies
    # (282.9 recorded vs a "301-371" band); the median is the honest
    # central number and the spread is the honest error bar.
    vals = sorted(r["goodput_mbps"] for r in results)
    med = statistics.median(vals)
    print(json.dumps({
        "metric": metric,
        "value": round(med, 1),
        "unit": "MB/s",
        "median_of": len(vals),
        "min": vals[0],
        "max": vals[-1],
        "spread_pct": round(100 * (vals[-1] - vals[0]) / med, 1) if med else None,
        "vs_baseline": None,
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blob-mb", type=int, default=256)
    ap.add_argument("--piece-kb", type=int, default=1024)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--skip-knockout", action="store_true",
                    help="skip the pump_ceiling_mbps (all-knockout) rows")
    ap.add_argument("--skip-alloc", action="store_true",
                    help="skip the tracemalloc recv_alloc_per_piece sample")
    ap.add_argument("--skip-brownout", action="store_true",
                    help="skip the hedged-read brown-out row")
    ap.add_argument("--skip-workers", action="store_true",
                    help="skip the workers_scaling + seed_cpu_per_byte"
                         " rows (multi-core data plane)")
    ap.add_argument("--skip-trace", action="store_true",
                    help="skip the trace_overhead (trace-off vs trace-on"
                         " at shipped sampling) rows")
    ap.add_argument("--skip-profiler", action="store_true",
                    help="skip the profiler_overhead (off vs on at"
                         " shipped hz) + leech_attribution rows")
    ap.add_argument("--flame-dir", default=None,
                    help="write the attribution run's profile dump here"
                         " (fold it with `kraken-tpu flame`)")
    ap.add_argument("--workers", type=int, default=0,
                    help="data_plane_workers for the headline rows (the"
                         " scaling rows always compare 0 vs 2)")
    args = ap.parse_args()

    _summarize(
        "pair_goodput_mbps",
        _run_repeats(args, knockout=False, workers=args.workers),
    )
    if not args.skip_knockout:
        _summarize(
            "pump_ceiling_mbps",
            _run_repeats(args, knockout=True, workers=args.workers),
        )
    if not args.skip_workers:
        run_workers_scaling(args)
        run_leech_workers_scaling(args)
        run_seed_serve(args)
    if not args.skip_trace:
        run_trace_overhead(args)
    if not args.skip_profiler:
        run_profiler_overhead(args)
        run_leech_attribution(args, flame_dir=args.flame_dir)
    if not args.skip_alloc:
        print(json.dumps(run_alloc_sample()))
    if not args.skip_brownout:
        print(json.dumps(run_brownout()))


if __name__ == "__main__":
    main()
