"""The pull path timed from inside the agent: a received piece's six stages
(``p2p_piece_stage_seconds``, p2p/dispatch.py) and the verifier's starved
time (``verify_starved_seconds_total``, p2p/storage.py).

- a two-node pull over real conns: six stages a piece, in order, summing
  to the piece's request -> next request on its conn; a sampled receive
  span carries them;
- a duplicate arrival books nothing;
- a device verifier books the stretch it sat with nothing in flight and
  nothing queued, and no time while a section runs; a ``cpu`` one books
  none.
"""

import asyncio
import os
import time

import pytest

from kraken_tpu.p2p.conn import Conn
from kraken_tpu.p2p.dispatch import PIECE_STAGES, Dispatcher, _Peer
from kraken_tpu.p2p.piecerequest import RequestManager
from kraken_tpu.p2p.storage import AgentTorrentArchive, BatchedVerifier
from kraken_tpu.p2p.wire import Message, MsgType
from kraken_tpu.store import CAStore
from kraken_tpu.utils import trace
from kraken_tpu.utils.metrics import REGISTRY
from kraken_tpu.utils.trace import TRACER, TraceConfig
from tests.test_p2p_units import (
    _FakeConn, _GatedHasher, _seeding_torrent, _sha, _until, make_metainfo,
    pid,
)


class _SlowDevice:
    """A device verifier's hasher whose section takes 10 ms, so that a
    stage booked twice or left out shows in a piece's sum."""

    name = "tpu"

    def hash_batch(self, pieces, purpose="verify"):
        import numpy as np

        time.sleep(0.01)
        return np.stack([np.frombuffer(_sha(p), dtype=np.uint8) for p in pieces])


class _Observed:
    """Stands in for the stage histogram: every observation, in order."""

    def __init__(self):
        self.seen: list[tuple[str, float]] = []

    def observe(self, value: float, stage: str) -> None:
        self.seen.append((stage, value))


@pytest.fixture
def sampled():
    cfg0, node0, hook0 = TRACER.config, TRACER.node, TRACER.on_record
    TRACER.recorder.clear()
    TRACER.apply(TraceConfig(sample_rate=1.0))
    yield TRACER
    TRACER.config, TRACER.node, TRACER.on_record = cfg0, node0, hook0
    TRACER.recorder.clear()


async def _conn_pair(info_hash):
    """(leecher's conn to the seeder, seeder's conn to the leecher) over
    one localhost socket, and the listener to close."""
    accepted = asyncio.get_running_loop().create_future()

    async def on_conn(reader, writer):
        accepted.set_result((reader, writer))

    server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    r1, w1 = await asyncio.open_connection("127.0.0.1", port)
    r2, w2 = await accepted
    return server, Conn(r1, w1, pid(1), info_hash), Conn(r2, w2, pid(2), info_hash)


def test_two_node_pull_books_six_stages_a_piece(tmp_path, sampled):
    blob = os.urandom(8 * 1024)
    mi = make_metainfo(blob)  # eight pieces of 1 KiB

    async def main():
        seed = _seeding_torrent(tmp_path / "seed", blob)
        leech = AgentTorrentArchive(
            CAStore(str(tmp_path / "leech")), BatchedVerifier(_SlowDevice())
        ).create_torrent(mi)
        with trace.span("p2p.download"):
            server, to_seeder, to_leecher = await _conn_pair(mi.info_hash)
            requests: list[tuple[int, float]] = []
            send = to_seeder.send

            async def sending(msg):
                if msg.type == MsgType.PIECE_REQUEST:
                    requests.append((msg.header["index"], time.monotonic()))
                await send(msg)

            to_seeder.send = sending
            seeder = Dispatcher(seed)
            # One request in flight: each piece's refill is the next request.
            leecher = Dispatcher(leech, requests=RequestManager(pipeline_limit=1))
            observed = leecher._h_stage = _Observed()
            to_seeder.start()
            to_leecher.start()
            assert seeder.add_conn(to_leecher, leech.bitfield(), mi.num_pieces)
            assert leecher.add_conn(to_seeder, seed.bitfield(), mi.num_pieces)
            await asyncio.wait_for(leecher.done, 10)
        seeder.close()
        leecher.close()
        server.close()
        await server.wait_closed()
        return requests, observed.seen, leecher.stage_split()

    requests, seen, split = asyncio.run(main())
    n = mi.num_pieces
    assert len(requests) == n  # no piece was asked for twice
    assert [stage for stage, _ in seen] == list(PIECE_STAGES) * n
    pieces = [dict(seen[k * 6:(k + 1) * 6]) for k in range(n)]
    assert all(s >= 0 for p in pieces for s in p.values())
    assert all(p["verify"] >= 0.01 for p in pieces)
    # Request -> clear_piece -> the next request on the conn, piece by piece.
    for k in range(n - 1):
        cycle = requests[k + 1][1] - requests[k][1]
        assert sum(pieces[k].values()) == pytest.approx(cycle, abs=1e-3), k
    assert pieces[-1]["refill"] == 0  # the last piece completes the blob
    # The pull's own split is fed from the same readings.
    assert split["piece_wait_s"] == pytest.approx(
        sum(p["wait"] + p["recv"] for p in pieces), abs=2e-3)
    spans = [s for s in sampled.recorder.snapshot()
             if s["name"] == "p2p.piece.receive"]
    assert len(spans) == n
    for span in spans:
        assert {f"{stage}_s" for stage in PIECE_STAGES} <= set(span["attrs"])


def test_duplicate_arrival_books_nothing(tmp_path):
    blob = os.urandom(3 * 1024)
    mi = make_metainfo(blob)

    async def main():
        leech = AgentTorrentArchive(
            CAStore(str(tmp_path / "leech")), BatchedVerifier()
        ).create_torrent(mi)
        d = Dispatcher(leech, requests=RequestManager(pipeline_limit=1))
        observed = d._h_stage = _Observed()
        conn = _FakeConn(pid(1))
        peer = _Peer(conn, set(range(mi.num_pieces)),
                     asyncio.get_running_loop().time())
        d._peers[conn.peer_id] = peer
        await d._request_more(peer)
        idx = conn.sent[0].header["index"]
        data = blob[idx * 1024:(idx + 1) * 1024]
        loop = asyncio.get_running_loop()
        await d._on_payload(peer, idx, Message.piece_payload(idx, data),
                            loop.time())
        assert len(observed.seen) == 6  # no header reading: recv is 0
        assert dict(observed.seen)["recv"] == 0
        await d._on_payload(peer, idx, Message.piece_payload(idx, data),
                            loop.time())
        assert len(observed.seen) == 6
        d.close()

    asyncio.run(main())


class _Clock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _starved() -> float:
    return REGISTRY.counter("verify_starved_seconds_total").value()


@pytest.mark.parametrize("kind, books", [("tpu", True), ("cpu", False)])
def test_verifier_books_only_the_stretch_with_nothing_in_flight(kind, books):
    async def main():
        clock = _Clock()
        h = _GatedHasher(kind)
        v = BatchedVerifier(h, clock=clock)
        s0 = _starved()
        first = asyncio.create_task(v.verify(b"p0", _sha(b"p0")))
        await _until(lambda: h.batches == [1])
        assert _starved() == s0  # the verifier's first piece books nothing
        clock.t = 101.0
        rest = [asyncio.create_task(v.verify(p, _sha(p))) for p in (b"a", b"b")]
        await asyncio.sleep(0.01)
        assert _starved() == s0  # a section is in flight: not starved
        clock.t = 102.0
        h.gate.set()
        assert all(await asyncio.wait_for(asyncio.gather(first, *rest), 10))
        await _until(lambda: not v._inflight)
        clock.t = 104.25
        assert await asyncio.wait_for(v.verify(b"x", _sha(b"x")), 10)
        assert _starved() - s0 == (2.25 if books else 0.0)

    asyncio.run(main())
