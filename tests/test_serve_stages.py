"""The serve side of a pull, timed on the node that serves: a served
piece's five stages (``p2p_serve_stage_seconds``, p2p/dispatch.py
``SERVE_STAGES``), booked by the conn's send loop once the payload is
written.

- a two-node pull over real conns delivers the seeded blob (hashlib) and
  books each of the five stages once a piece served, each >= 0, their sum
  inside the requester's wall from its request to the piece written; a
  sampled serve span carries the stages known by its end;
- ``shape`` is the egress bucket's wait: > 0 under a small cap, exactly 0
  with none;
- a serve cancelled in its read, and one whose payload is never written,
  book nothing.
"""

import asyncio
import hashlib
import os
import time

import pytest

from kraken_tpu.p2p.dispatch import SERVE_STAGES, Dispatcher, _Peer
from kraken_tpu.p2p.piecerequest import RequestManager
from kraken_tpu.p2p.storage import (
    AgentTorrentArchive, BatchedVerifier, OriginTorrentArchive,
)
from kraken_tpu.store import CAStore
from kraken_tpu.utils import trace
from kraken_tpu.utils.bandwidth import BandwidthLimiter
from tests.test_p2p_units import make_metainfo
from tests.test_pull_stages import _conn_pair, _Observed, sampled  # noqa: F401

MiB = 1 << 20


def _seeder(tmp_path, blob: bytes, mi):
    store = CAStore(str(tmp_path / "seed"))
    store.create_cache_file(mi.digest, iter([blob]))
    return OriginTorrentArchive(store, BatchedVerifier()).create_torrent(mi)


def _pull(tmp_path, blob: bytes, piece_length: int, egress_bps: float):
    """Pull ``blob`` from a seeder whose conn to the leecher is capped at
    ``egress_bps`` (0: no cap), one request in flight. Returns the bytes
    delivered, the seeder's serve stages one dict a piece, and the
    leecher's receive stages one dict a piece, in piece order."""
    mi = make_metainfo(blob, piece_length)

    async def main():
        seed = _seeder(tmp_path, blob, mi)
        leech = AgentTorrentArchive(
            CAStore(str(tmp_path / "leech")), BatchedVerifier()
        ).create_torrent(mi)
        with trace.span("p2p.download"):
            server, to_seeder, to_leecher = await _conn_pair(mi.info_hash)
            if egress_bps:
                to_leecher._bw = BandwidthLimiter(egress_bps=egress_bps)
            seeder = Dispatcher(seed)
            served = seeder._h_serve = _Observed()
            leecher = Dispatcher(leech, requests=RequestManager(pipeline_limit=1))
            received = leecher._h_stage = _Observed()
            to_seeder.start()
            to_leecher.start()
            assert seeder.add_conn(to_leecher, leech.bitfield(), mi.num_pieces)
            assert leecher.add_conn(to_seeder, seed.bitfield(), mi.num_pieces)
            await asyncio.wait_for(leecher.done, 30)
        # The last payload's booking runs right after its drain.
        deadline = time.monotonic() + 5
        while len(served.seen) < len(SERVE_STAGES) * mi.num_pieces:
            assert time.monotonic() < deadline
            await asyncio.sleep(0.001)
        delivered = b"".join(leech.read_piece(i) for i in range(mi.num_pieces))
        seeder.close()
        leecher.close()
        server.close()
        await server.wait_closed()
        return delivered, served.seen, received.seen

    delivered, served, received = asyncio.run(main())
    n, k = mi.num_pieces, len(SERVE_STAGES)
    assert [stage for stage, _ in served] == list(SERVE_STAGES) * n
    return (delivered,
            [dict(served[i * k:(i + 1) * k]) for i in range(n)],
            [dict(received[i * 6:(i + 1) * 6]) for i in range(n)])


def test_two_node_pull_books_five_serve_stages_a_piece(tmp_path, sampled):
    blob = os.urandom(8 * 1024 + 100)  # eight 1 KiB pieces and a tail
    delivered, served, received = _pull(tmp_path, blob, 1024, 0)
    assert hashlib.sha256(delivered).digest() == hashlib.sha256(blob).digest()
    assert len(served) == 9
    spans = [s for s in sampled.recorder.snapshot() if s["name"] == "p2p.piece.serve"]
    assert len(spans) == 9
    for span, stages in zip(spans, served):  # one piece at a time, in order
        assert span["attrs"]["admit_s"] == round(stages["admit"], 6)
        assert span["attrs"]["read_s"] == round(stages["read"], 6)
    for k, (stages, cycle) in enumerate(zip(served, received)):
        assert all(s >= 0 for s in stages.values()), k
        assert stages["shape"] == 0, k  # no bucket, no clock read for it
        # The serve sits inside the requester's request -> piece written.
        wall = sum(cycle[s] for s in ("wait", "recv", "queue", "verify", "write"))
        assert sum(stages.values()) <= wall, k


def test_egress_cap_books_its_wait_as_shape(tmp_path):
    # 8 MiB at 4 MiB/s: the bucket's first second passes at once, the rest
    # of the payload waits about a second in all.
    blob = os.urandom(8 * MiB)
    delivered, served, received = _pull(tmp_path, blob, MiB, 4 * MiB)
    assert hashlib.sha256(delivered).digest() == hashlib.sha256(blob).digest()
    assert all(s >= 0 for stages in served for s in stages.values())
    assert sum(stages["shape"] for stages in served) > 0.5
    for stages, cycle in zip(served, received):
        wall = sum(cycle[s] for s in ("wait", "recv", "queue", "verify", "write"))
        assert sum(stages.values()) <= wall


@pytest.mark.parametrize("how", ["cancelled_in_read", "never_written"])
def test_serve_that_does_not_end_books_nothing(tmp_path, how):
    blob = os.urandom(4 * 1024)
    mi = make_metainfo(blob)

    async def main():
        seed = _seeder(tmp_path, blob, mi)
        server, to_seeder, to_leecher = await _conn_pair(mi.info_hash)
        d = Dispatcher(seed)
        served = d._h_serve = _Observed()
        peer = _Peer(to_leecher, set(), asyncio.get_running_loop().time())
        d._peers[to_leecher.peer_id] = peer
        if how == "cancelled_in_read":
            gate = asyncio.Event()

            async def stuck(i):
                await gate.wait()

            seed.read_piece_async = stuck
            to_leecher.start()
            d._admit_serve(peer, 0, None, time.monotonic())
            await asyncio.sleep(0.01)
            (task,) = d._io_tasks
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
        else:
            # The conn's send loop never runs: the payload is queued and
            # dies with the conn.
            d._admit_serve(peer, 0, None, time.monotonic())
            (task,) = d._io_tasks
            await task
            to_leecher.close()
        await asyncio.sleep(0.01)
        assert peer.serving == 0
        d.close()
        to_seeder.close()
        server.close()
        await server.wait_closed()
        return served.seen

    assert asyncio.run(main()) == []
