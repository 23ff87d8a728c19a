"""Zero-copy wire plane tests (round 7): framing edge cases, the payload
buffer pool's lease lifecycle (leak detector), payload-length enforcement
as peer misbehavior, conn close reasons, and the per-piece allocation
regression pin on the recv path.

The allocation pin is the CI tooth behind the zero-copy claim: a future
refactor that quietly reintroduces a payload copy between the socket and
``os.pwrite`` (the round-5 ``raw[header_len:]`` slice cost a full payload
per piece) fails here, not in a quarterly profile. The leak tests close
the other trap: a pooled buffer is only zero-copy if EVERY path -- happy,
corrupt-piece ban, mid-transfer disconnect -- returns its lease.
"""

import asyncio
import os

import msgpack
import pytest

from kraken_tpu.core.digest import Digest
from kraken_tpu.p2p import wire
from kraken_tpu.p2p.conn import Conn, ConnClosedError
from kraken_tpu.p2p.wire import (
    Message,
    MsgType,
    PayloadOversizeError,
    WireError,
    recv_message,
    send_message,
    send_messages,
)
from kraken_tpu.utils import failpoints
from kraken_tpu.utils.bufpool import MIN_CLASS, BufferPool, _class_for
from tests.test_swarm import (
    FakeTracker, NS, make_metainfo, make_peer, start_all, stop_all,
)


def pid(i: int):
    from kraken_tpu.core.peer import PeerID

    return PeerID((bytes([i]) * 20).hex())


class Sink:
    """StreamWriter-shaped byte sink for offline framing."""

    def __init__(self):
        self.buf = bytearray()

    def write(self, b):
        self.buf += b

    def writelines(self, bufs):
        for b in bufs:
            self.buf += b

    async def drain(self):
        pass


async def frame_bytes(*msgs: Message) -> bytes:
    sink = Sink()
    await send_messages(sink, msgs)
    return bytes(sink.buf)


async def feed(raw: bytes, pool=None, max_payload=wire.MAX_PAYLOAD) -> Message:
    reader = asyncio.StreamReader()
    reader.feed_data(raw)
    reader.feed_eof()
    return await recv_message(reader, pool=pool, max_payload=max_payload)


# -- framing edge cases ------------------------------------------------------


def test_roundtrip_all_types_boundary_payloads():
    """Every message type, with payload sizes at the interesting
    boundaries (empty, 1, one-under/at/one-over a pool size class),
    batched through ONE corked send_messages call and recovered intact --
    the vectored path must preserve framing exactly."""

    async def main():
        pool = BufferPool()
        sizes = [0, 1, MIN_CLASS - 1, MIN_CLASS, MIN_CLASS + 1, 100_000]
        msgs = []
        for i, n in enumerate(sizes):
            msgs.append(Message.piece_payload(i, os.urandom(n)))
        msgs += [
            Message.handshake("ab" * 20, "cd" * 32, "ef" * 32, "ns", b"\x01", 8),
            Message.bitfield(b"\x0f", 4),
            Message.piece_request(7),
            Message.announce_piece(7),
            Message.cancel_piece(3),
            Message.complete(),
            Message.error("busy", "try later"),
        ]
        raw = await frame_bytes(*msgs)
        reader = asyncio.StreamReader()
        reader.feed_data(raw)
        reader.feed_eof()
        for sent in msgs:
            got = await recv_message(reader, pool=pool)
            assert got.type == sent.type
            assert got.header == sent.header
            assert bytes(got.payload) == bytes(sent.payload)
            if sent.type == MsgType.PIECE_PAYLOAD and sent.payload:
                assert isinstance(got.payload, memoryview)
            got.release()
        assert pool.leased == 0

    asyncio.run(main())


def test_max_header_exact_and_off_by_one(monkeypatch):
    """A header of exactly MAX_HEADER parses; one byte more is a
    WireError. Hand-built frames so the boundary is byte-exact."""
    monkeypatch.setattr(wire, "MAX_HEADER", 256)

    def frame_with_header_len(target: int) -> bytes:
        # msgpack str-length encoding widens at size breakpoints; search
        # the pad that lands byte-exact on the target.
        pad = target - len(msgpack.packb({"p": ""}))
        while len(msgpack.packb({"p": "x" * pad})) > target:
            pad -= 1
        header = msgpack.packb({"p": "x" * pad})
        assert len(header) == target
        return (
            bytes([MsgType.PIECE_REQUEST])
            + len(header).to_bytes(4, "big")
            + (0).to_bytes(4, "big")
            + header
        )

    async def main():
        got = await feed(frame_with_header_len(256))
        assert got.type == MsgType.PIECE_REQUEST
        with pytest.raises(WireError):
            await feed(frame_with_header_len(257))

    asyncio.run(main())


def test_max_payload_exact_and_off_by_one(monkeypatch):
    monkeypatch.setattr(wire, "MAX_PAYLOAD", 1 << 16)

    async def main():
        ok = await frame_bytes(Message.piece_payload(0, b"x" * (1 << 16)))
        got = await feed(ok)
        assert len(got.payload) == 1 << 16
        over = await frame_bytes(Message.piece_payload(0, b"x" * ((1 << 16) + 1)))
        with pytest.raises(PayloadOversizeError):
            await feed(over)
        # Non-payload types hit the generic oversize error instead.
        raw = (
            bytes([MsgType.BITFIELD])
            + (0).to_bytes(4, "big")
            + ((1 << 16) + 1).to_bytes(4, "big")
        )
        with pytest.raises(WireError):
            await feed(raw)

    asyncio.run(main())


def test_truncation_at_every_boundary():
    """EOF mid-prefix, mid-header, and mid-payload (every prefix offset,
    the header edge, one-into-payload, one-short-of-complete) must all
    surface as WireError -- and a truncated POOLED payload must return
    its lease (the reader died holding a leased buffer)."""

    async def main():
        payload = os.urandom(100)
        raw = await frame_bytes(Message.piece_payload(3, payload))
        header_len = int.from_bytes(raw[1:5], "big")
        cuts = list(range(1, 9))                       # mid-prefix
        cuts += [9 + header_len // 2, 9 + header_len]  # mid/at header
        cuts += [9 + header_len + 1, len(raw) - 1]     # mid-payload
        pool = BufferPool()
        for cut in cuts:
            with pytest.raises(WireError):
                await feed(raw[:cut], pool=pool)
            assert pool.leased == 0, f"lease leaked at cut {cut}"

    asyncio.run(main())


def test_payload_oversize_rejected_before_buffering():
    """The oversize check runs on the PREFIX: no payload byte is read and
    no buffer is leased, so a hostile length cannot balloon RSS."""

    async def main():
        pool = BufferPool()
        # Prefix claims 1 MiB payload against a 64 KiB piece-length bound;
        # deliver only the prefix+header -- the error must fire anyway.
        header = msgpack.packb({"index": 0})
        raw = (
            bytes([MsgType.PIECE_PAYLOAD])
            + len(header).to_bytes(4, "big")
            + (1 << 20).to_bytes(4, "big")
            + header
        )
        reader = asyncio.StreamReader()
        reader.feed_data(raw)  # no EOF: a read past the prefix would hang
        with pytest.raises(PayloadOversizeError):
            await asyncio.wait_for(
                recv_message(reader, pool=pool, max_payload=64 << 10), 2.0
            )
        assert pool.leased == 0 and pool.hits + pool.misses == 0

    asyncio.run(main())


# -- bufpool -----------------------------------------------------------------


def test_bufpool_size_classes_reuse_and_budget():
    pool = BufferPool(budget_bytes=2 * MIN_CLASS)
    assert _class_for(1) == MIN_CLASS
    assert _class_for(MIN_CLASS + 1) == 2 * MIN_CLASS

    a = pool.lease(100)
    assert len(a.view) == 100 and pool.leased == 1 and pool.misses == 1
    a.release()
    assert pool.leased == 0 and pool.retained_bytes == MIN_CLASS
    b = pool.lease(200)  # same class: reused
    assert pool.hits == 1 and pool.allocated == 1
    # Idempotent release: double release must not double-return.
    b.release()
    b.release()
    assert pool.retained_bytes == MIN_CLASS

    # Budget cap: releases beyond it drop to the allocator.
    c, d, e = pool.lease(10), pool.lease(10), pool.lease(10)
    for lease in (c, d, e):
        lease.release()
    assert pool.retained_bytes <= 2 * MIN_CLASS
    # Live shrink applies on the next release cycle.
    pool.set_budget(0)
    pool.lease(10).release()
    f = pool.lease(10)
    f.release()
    assert pool.retained_bytes == 0


def test_bufpool_use_after_release_is_loud():
    pool = BufferPool()
    lease = pool.lease(50)
    view = lease.view
    view[0] = 7
    lease.release()
    with pytest.raises(ValueError):
        view[0]  # released exporter: loud, not recycled-bytes corruption


# -- conn: close reasons, fast paths, misbehavior ----------------------------


async def _conn_pair(**kw):
    """Real loopback socket pair; returns (conn, remote_reader,
    remote_writer, server)."""
    accepted: asyncio.Future = asyncio.get_running_loop().create_future()

    async def on_accept(r, w):
        accepted.set_result((r, w))

    server = await asyncio.start_server(on_accept, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    rr, rw = await accepted
    from kraken_tpu.core.metainfo import InfoHash

    conn = Conn(
        reader, writer, pid(1), InfoHash(("ab" * 32)), **kw
    )
    conn.start()
    return conn, rr, rw, server


def test_conn_construct_without_running_loop():
    """Conn.__init__ must not touch the event loop (the deprecated
    get_event_loop() crashed here under a non-running loop on 3.12+);
    ``closed`` materializes lazily on the running loop."""
    async def make_reader():
        return asyncio.StreamReader()

    # Built on a loop that is CLOSED by the time Conn constructs below --
    # exactly the post-asyncio.run context where get_event_loop() raises.
    r = asyncio.run(make_reader())

    class W:
        def close(self):
            pass

    conn = Conn(r, W(), pid(1), __import__(
        "kraken_tpu.core.metainfo", fromlist=["InfoHash"]
    ).InfoHash("ab" * 32))
    assert conn._closed_fut is None
    conn.close(reason="test")  # no loop: records reason, skips the future
    assert conn.close_reason == "test"


def test_conn_oversize_payload_is_misbehavior():
    """A PIECE_PAYLOAD longer than the handshaken piece length closes the
    conn with reason=oversize_payload and flags misbehavior -- the
    dispatcher escalates that to the blacklist."""

    async def main():
        from kraken_tpu.utils.metrics import REGISTRY

        counter = REGISTRY.counter("conn_closed_total")
        before = counter.value(reason="oversize_payload")
        conn, rr, rw, server = await _conn_pair(
            pool=BufferPool(), max_payload_length=4096
        )
        try:
            await send_message(rw, Message.piece_payload(0, b"x" * 8192))
            await asyncio.wait_for(conn.wait_closed(), 5.0)
            assert conn.close_reason == "oversize_payload"
            assert conn.misbehavior
            assert counter.value(reason="oversize_payload") == before + 1
            with pytest.raises(ConnClosedError):
                await conn.recv()
        finally:
            conn.close()
            rw.close()
            server.close()
            await server.wait_closed()

    asyncio.run(main())


def test_conn_records_remote_close_reason():
    async def main():
        conn, rr, rw, server = await _conn_pair()
        try:
            rw.close()
            await asyncio.wait_for(conn.wait_closed(), 5.0)
            # Remote FIN surfaces as a wire error ("connection closed").
            assert conn.close_reason in ("wire_error", "connection_error")
            assert conn.close_detail
        finally:
            conn.close()
            server.close()
            await server.wait_closed()

    asyncio.run(main())


def test_conn_fast_paths_roundtrip_and_cork():
    """send() fast path (put_nowait) + the corked send loop must deliver
    a burst of mixed control/payload frames intact through one socket,
    and recv() must take its get_nowait fast path for buffered frames."""

    async def main():
        conn, rr, rw, server = await _conn_pair(send_batch=8)
        try:
            payload = os.urandom(20_000)
            msgs = [Message.piece_request(i) for i in range(5)]
            msgs += [Message.piece_payload(9, payload)]
            msgs += [Message.announce_piece(3), Message.complete()]
            for m in msgs:  # all fast-path enqueues, drained as batches
                await conn.send(m)
            got = []
            for _ in msgs:
                got.append(await recv_message(rr))
            assert [m.type for m in got] == [m.type for m in msgs]
            assert bytes(got[5].payload) == payload
            assert conn.bytes_sent == sum(len(m.payload) for m in msgs)

            # Inbound: push two frames, then recv twice -- the second
            # recv hits the buffered fast path.
            await send_message(rw, Message.announce_piece(1))
            await send_message(rw, Message.announce_piece(2))
            a = await conn.recv()
            b = await conn.recv()
            assert {a.header["index"], b.header["index"]} == {1, 2}
        finally:
            conn.close()
            rw.close()
            server.close()
            await server.wait_closed()

    asyncio.run(main())


def test_dispatcher_blacklists_misbehaving_conn(tmp_path):
    """_fail_peer must convert a misbehavior-flagged conn close into a
    reasoned drop (-> escalating blacklist), and a plain close into a
    reasonless one (-> free redial)."""
    from kraken_tpu.p2p.dispatch import Dispatcher, _Peer
    from tests.test_p2p_units import _seeding_torrent

    async def main():
        failures = []
        t = _seeding_torrent(tmp_path, os.urandom(4096))
        d = Dispatcher(t, on_peer_failure=lambda p, r: failures.append((p, r)))

        class FakeConn:
            def __init__(self, peer_id, misbehavior):
                self.peer_id = peer_id
                self.misbehavior = misbehavior
                self.close_reason = "oversize_payload" if misbehavior else None

            def close(self):
                pass

        bad, good = FakeConn(pid(1), True), FakeConn(pid(2), False)
        now = asyncio.get_running_loop().time()
        d._peers[bad.peer_id] = _Peer(bad, set(), now)
        d._peers[good.peer_id] = _Peer(good, set(), now)
        d._fail_peer(bad.peer_id, ConnClosedError("x"))
        d._fail_peer(good.peer_id, ConnClosedError("x"))
        assert [p for p, _ in failures] == [bad.peer_id]
        assert "oversize_payload" in failures[0][1]
        d.close()

    asyncio.run(main())


def test_payload_flood_bound_sheds_and_releases(tmp_path):
    """Unsolicited PIECE_PAYLOAD flood: admission caps concurrent payload
    tasks per peer (_MAX_RECEIVING_PER_PEER) and sheds over-cap frames by
    RELEASING their pooled buffers -- a hostile pusher gets no unbounded
    lease growth, and the hot-path bypass (which never blocks on the recv
    queue) cannot be used to balloon RSS. Mirrors the serve-side flood
    test: frames arrive back-to-back without yielding to the loop."""
    from kraken_tpu.core.hasher import get_hasher
    from kraken_tpu.core.metainfo import MetaInfo
    from kraken_tpu.p2p.dispatch import Dispatcher, _Peer
    from kraken_tpu.p2p.storage import AgentTorrentArchive, BatchedVerifier
    from kraken_tpu.store import CAStore

    async def main():
        blob = os.urandom(256 * 4096)
        hashes = get_hasher("cpu").hash_pieces(blob, 4096)
        mi = MetaInfo(Digest.from_bytes(blob), len(blob), 4096, hashes.tobytes())
        store = CAStore(str(tmp_path / "s"))
        t = AgentTorrentArchive(store, BatchedVerifier()).create_torrent(mi)
        d = Dispatcher(t)
        hold = asyncio.Event()

        async def parked(self, peer, idx, msg, handed):
            await hold.wait()

        d._on_payload = parked.__get__(d)

        class FakeConn:
            peer_id = pid(1)
            misbehavior = False

            def close(self):
                pass

        peer = _Peer(FakeConn(), set(), asyncio.get_running_loop().time())
        d._peers[peer.conn.peer_id] = peer
        pool = BufferPool()
        n = 200
        for i in range(n):
            lease = pool.lease(4096)
            msg = Message(
                MsgType.PIECE_PAYLOAD, {"index": i}, lease.view, lease=lease
            )
            d._handle_payload_direct(peer, msg)
        cap = Dispatcher._MAX_RECEIVING_PER_PEER
        assert peer.receiving == cap
        assert pool.leased == cap  # over-cap frames shed AND released
        hold.set()
        for _ in range(100):
            if pool.leased == 0:
                break
            await asyncio.sleep(0.01)
        assert pool.leased == 0 and peer.receiving == 0
        d.close()

    asyncio.run(main())


# -- leak detector: every lease returns, even on the failure paths -----------


@pytest.fixture
def chaos_plane():
    failpoints.FAILPOINTS.disarm_all()
    yield failpoints.FAILPOINTS
    failpoints.FAILPOINTS.disarm_all()


async def _drain_leases(scheds, timeout=5.0):
    deadline = asyncio.get_running_loop().time() + timeout
    def leased():
        return sum(s._bufpool.leased for s in scheds)
    while leased() and asyncio.get_running_loop().time() < deadline:
        await asyncio.sleep(0.05)
    return leased()


def test_bufpool_no_leak_happy_path(tmp_path):
    async def main():
        blob = os.urandom(300_000)
        mi = make_metainfo(blob, piece_length=16 * 1024)
        tracker = FakeTracker()
        tracker.metainfos[mi.digest.hex] = mi
        seeder, _ = make_peer(tmp_path, "seeder", tracker, seed_blob=blob)
        leecher, lstore = make_peer(tmp_path, "leecher", tracker)
        await start_all(seeder, leecher)
        try:
            seeder.seed(mi, NS)
            await asyncio.wait_for(leecher.download(NS, mi.digest), 30)
            assert lstore.read_cache_file(mi.digest) == blob
            assert await _drain_leases([seeder, leecher]) == 0
            pool = leecher._bufpool
            assert pool.hits + pool.misses >= mi.num_pieces
        finally:
            await stop_all(seeder, leecher)

    asyncio.run(main())


def test_bufpool_no_leak_corrupt_ban_path(tmp_path, chaos_plane):
    """The corrupt-piece -> PieceError -> peer-ban path must return the
    poisoned buffer too (the failpoint mutates the POOLED buffer in
    place), and the pull still completes bit-identical from the healthy
    seeder."""

    async def main():
        blob = os.urandom(400_000)  # 25 pieces
        mi = make_metainfo(blob, piece_length=16 * 1024)
        tracker = FakeTracker()
        tracker.metainfos[mi.digest.hex] = mi
        s1, _ = make_peer(tmp_path, "seed1", tracker, seed_blob=blob)
        s2, _ = make_peer(tmp_path, "seed2", tracker, seed_blob=blob)
        leecher, lstore = make_peer(tmp_path, "leecher", tracker)
        await start_all(s1, s2, leecher)
        try:
            s1.seed(mi, NS)
            s2.seed(mi, NS)
            chaos_plane.arm("p2p.conn.recv.corrupt", "once")
            await asyncio.wait_for(leecher.download(NS, mi.digest), 30)
            assert lstore.read_cache_file(mi.digest) == blob
            # The corrupting peer got hard-blacklisted...
            assert leecher.conn_state.blacklist._entries
            # ...and no lease leaked, including the banned frame's.
            assert await _drain_leases([s1, s2, leecher]) == 0
        finally:
            await stop_all(s1, s2, leecher)

    asyncio.run(main())


def test_bufpool_no_leak_mid_transfer_disconnect(tmp_path, chaos_plane):
    """A conn dropped mid-transfer (frames parked in queues, io tasks in
    flight) must return every lease; the re-dial completes the pull."""

    async def main():
        blob = os.urandom(400_000)
        mi = make_metainfo(blob, piece_length=16 * 1024)
        tracker = FakeTracker()
        tracker.metainfos[mi.digest.hex] = mi
        seeder, _ = make_peer(tmp_path, "seeder", tracker, seed_blob=blob)
        leecher, lstore = make_peer(tmp_path, "leecher", tracker)
        await start_all(seeder, leecher)
        try:
            seeder.seed(mi, NS)
            chaos_plane.arm("p2p.conn.disconnect", "once")
            await asyncio.wait_for(leecher.download(NS, mi.digest), 30)
            assert lstore.read_cache_file(mi.digest) == blob
            assert await _drain_leases([seeder, leecher]) == 0
        finally:
            await stop_all(seeder, leecher)

    asyncio.run(main())


# -- the allocation regression pin (CI tooth for the zero-copy claim) --------


def test_recv_path_allocation_pin():
    """tracemalloc sample (shared with bench_pair.run_alloc_sample):
    bytes charged to p2p/wire.py per received piece, measured while each
    decoded message is still live. The round-5 slice copy charged a FULL
    payload per piece (fraction ~1.0); the pooled path must stay under a
    generous 0.25 -- anything above means a payload-scale allocation
    crept back in between the socket and os.pwrite."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
    from bench_pair import run_alloc_sample

    r = run_alloc_sample(pieces=8, piece_kb=256)
    assert r["payload_fraction"] < 0.25, r
    # Block count stays O(1) per frame (Message + header + view), never
    # O(payload): a generous 20-block band.
    assert r["wire_blocks_per_piece"] < 20, r
    # And the pool actually recycled: one warm buffer served every frame.
    assert r["pool_allocated"] == 1, r


def test_loopback_pull_reuses_buffers():
    """End-to-end allocation accounting on a real loopback pull: the pool
    must serve most pieces from recycled buffers (allocated << pieces)
    and leak nothing -- the in-flight bound is conns x pipeline depth,
    not O(pieces)."""
    import pathlib
    import sys
    import tempfile

    sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
    from bench_pair import run_pair

    with tempfile.TemporaryDirectory() as root:
        r = asyncio.run(run_pair(8, 64, root))  # 128 pieces
    assert r["bufpool_leaked"] == 0, r
    assert r["bufpool_leases"] >= r["pieces"], r
    # Generous band: steady-state in-flight is <= pipeline depth (16),
    # but a slow verify ramp can briefly overshoot. Half the pieces is
    # the line between "pooled" and "allocating per piece".
    assert r["bufpool_allocated"] <= r["pieces"] / 2, r
    assert r["bufpool_hit_ratio"] > 0.5, r
