"""Origin ingest fast path (round 5, VERDICT r4 #2/#6).

The chunked-upload flow now computes the blob digest AND (CPU-hasher
origins) the per-piece hashes while the bytes stream in, so commit is a
rename -- no re-read, no second hash pass. These tests pin the
correctness edges of that optimization:

- stream-time MetaInfo is bit-identical to the windowed generate() pass;
- out-of-order PATCHes invalidate the tracker and commit falls back to
  the verifying re-read (wrong bytes still rejected);
- a final size that lands in a different piece-length tier than the
  stream-time bet falls back to generate();
- durability="fsync" commits survive and cost only the sync.
"""

import asyncio
import hashlib

import pytest
from aiohttp import ClientSession

from kraken_tpu.assembly import OriginNode
from kraken_tpu.core.digest import SHA256, Digest
from kraken_tpu.core.hasher import get_hasher
from kraken_tpu.origin.metainfogen import (
    Generator, PieceLengthConfig, TorrentMetaMetadata,
)

PIECE = 64 * 1024


def _node(tmp_path, **kw):
    kw.setdefault("piece_lengths", PieceLengthConfig(table=((0, PIECE),)))
    return OriginNode(store_root=str(tmp_path / "o"), dedup=False, **kw)


async def _upload(addr, d, chunks, offsets=None):
    """Drive the chunked-upload API; offsets override the sequential
    default to simulate out-of-order clients."""
    base = f"http://{addr}/namespace/ns/blobs/{d}"
    async with ClientSession() as http:
        async with http.post(f"{base}/uploads") as r:
            assert r.status == 200
            uid = await r.text()
        pos = 0
        for i, chunk in enumerate(chunks):
            off = pos if offsets is None else offsets[i]
            async with http.patch(
                f"{base}/uploads/{uid}",
                data=chunk,
                headers={"X-Upload-Offset": str(off)},
            ) as r:
                assert r.status == 204
            pos += len(chunk)
        async with http.put(f"{base}/uploads/{uid}/commit") as r:
            body = await r.text()
            return r.status, body


def test_stream_metainfo_matches_generate(tmp_path):
    """The stream-hashed MetaInfo must be byte-identical to what the
    windowed generate() pass would produce -- agents hash-verify every
    piece against it, so any drift bricks downloads."""

    async def main():
        import os

        blob = os.urandom(5 * PIECE + 1234)  # non-multiple: short last piece
        d = Digest.from_bytes(blob)
        node = _node(tmp_path)
        await node.start()
        try:
            status, _ = await _upload(
                node.addr, d, [blob[: 2 * PIECE], blob[2 * PIECE :]]
            )
            assert status == 201
            stored = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            # Independent oracle: hash pieces directly.
            want = get_hasher("cpu").hash_pieces(blob, PIECE).tobytes()
            assert stored.serialize() == type(stored)(
                d, len(blob), PIECE, want
            ).serialize()
            # And the generate() path agrees after wiping the sidecar.
            node.store.delete_metadata(d, TorrentMetaMetadata)
            regen = node.generator.generate_sync(d)
            assert regen.serialize() == stored.serialize()
        finally:
            await node.stop()

    asyncio.run(main())


def test_stream_metainfo_matches_generate_pooled(tmp_path):
    """hash_workers=2: stream-time pieces are hashed on pool workers in
    piece order while the blob digest streams serially -- the MetaInfo
    must still be byte-identical to the serial oracle, including across
    chunk boundaries that straddle pieces and a short trailing piece."""

    async def main():
        import os

        blob = os.urandom(9 * PIECE + 1234)
        d = Digest.from_bytes(blob)
        node = _node(tmp_path, hash_workers=2)
        await node.start()
        try:
            # Deliberately piece-misaligned chunk boundaries.
            cuts = [0, PIECE // 3, 4 * PIECE + 17, 7 * PIECE - 1, len(blob)]
            chunks = [blob[a:b] for a, b in zip(cuts, cuts[1:])]
            status, _ = await _upload(node.addr, d, chunks)
            assert status == 201
            stored = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            want = get_hasher("cpu").hash_pieces(blob, PIECE).tobytes()
            assert stored.serialize() == type(stored)(
                d, len(blob), PIECE, want
            ).serialize()
        finally:
            await node.stop()

    asyncio.run(main())


def test_patch_failure_invalidates_tracker(tmp_path):
    """An exception escaping the spool-file close (deferred write error,
    e.g. ENOSPC at flush) must invalidate the upload digest tracker: a
    client that carries on as if the PATCH landed must get the verifying
    re-read at commit, never the fast path over a possible hole
    (round-5 ADVICE, medium)."""

    async def main():
        import os

        from kraken_tpu.core.digest import Digest as D

        blob = os.urandom(2 * PIECE)
        d = Digest.from_bytes(blob)
        node = _node(tmp_path)
        await node.start()

        class FailingClose:
            def __init__(self, f):
                self._f = f

            def __getattr__(self, a):
                return getattr(self._f, a)

            def close(self):
                self._f.close()
                raise OSError("deferred write error at close")

        orig_open = node.store.open_upload_file
        patches = {"n": 0}

        def open_patched(uid):
            patches["n"] += 1
            f = orig_open(uid)
            return FailingClose(f) if patches["n"] == 1 else f

        node.store.open_upload_file = open_patched
        reads = {"n": 0}
        orig_reader = D.from_reader.__func__

        def counting_reader(cls, f):
            reads["n"] += 1
            return orig_reader(cls, f)

        D.from_reader = classmethod(counting_reader)
        try:

            base = f"http://{node.addr}/namespace/ns/blobs/{d}"
            async with ClientSession() as http:
                async with http.post(f"{base}/uploads") as r:
                    uid = await r.text()
                # First PATCH: bytes land, close raises -> 500.
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[:PIECE],
                    headers={"X-Upload-Offset": "0"},
                ) as r:
                    assert r.status == 500
                # Client believes it landed and streams on sequentially.
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[PIECE:],
                    headers={"X-Upload-Offset": str(PIECE)},
                ) as r:
                    assert r.status == 204
                async with http.put(f"{base}/uploads/{uid}/commit") as r:
                    assert r.status == 201, await r.text()
            # Commit must have taken the verifying re-read, not the
            # invalidated tracker's fast path.
            assert reads["n"] >= 1
            assert node.store.read_cache_file(d) == blob
        finally:
            D.from_reader = classmethod(orig_reader)
            await node.stop()

    asyncio.run(main())


def test_invalidated_pooled_tracker_drops_chunk_pins():
    """A pooled tracker buffers memoryview slices of request-body chunks
    until their piece completes; invalidation (PATCH failure, offset
    mismatch) must drop those pins -- an invalidated tracker can sit in
    the map for the 6h TTL, and each view keeps its whole parent chunk
    alive."""
    import io

    from kraken_tpu.core.hasher import HashPool
    from kraken_tpu.origin.server import _UploadDigest

    pool = HashPool(1, name="cpu/test-pins")
    t = _UploadDigest(piece_length=4096, pool=pool)
    t.begin_patch(0)
    t.write_and_update(io.BytesIO(), b"x" * 1000)  # partial piece buffered
    assert t._parts
    t.end_patch()
    t.invalidate()
    assert not t._parts and not t._futs
    # And the offset-mismatch path drops them too.
    t2 = _UploadDigest(piece_length=4096, pool=pool)
    t2.begin_patch(0)
    t2.write_and_update(io.BytesIO(), b"y" * 1000)
    t2.end_patch()
    assert not t2.begin_patch(999)  # wrong offset -> invalidate
    assert not t2._parts


def test_out_of_order_patches_fall_back_and_verify(tmp_path):
    """Reverse-order PATCHes break the running digest; commit must fall
    back to the verifying re-read and still land correctly -- and a
    WRONG body must still be rejected 400 on that path."""

    async def main():
        import os

        blob = os.urandom(3 * PIECE)
        d = Digest.from_bytes(blob)
        node = _node(tmp_path)
        await node.start()
        try:
            # Chunks sent out of order (second half first).
            status, _ = await _upload(
                node.addr, d,
                [blob[2 * PIECE :], blob[: 2 * PIECE]],
                offsets=[2 * PIECE, 0],
            )
            assert status == 201
            assert node.store.read_cache_file(d) == blob

            # Wrong bytes, claimed digest: rejected on the re-read path.
            other = os.urandom(PIECE)
            wrong_d = Digest.from_bytes(os.urandom(32))
            status, body = await _upload(
                node.addr, wrong_d, [other[PIECE // 2 :], other[: PIECE // 2]],
                offsets=[PIECE // 2, 0],
            )
            assert status == 400, body
        finally:
            await node.stop()

    asyncio.run(main())


def test_wrong_digest_rejected_on_stream_path(tmp_path):
    """Sequential upload (stream digest valid) with a lying digest in the
    URL: the precomputed hash must cause the 400, without a re-read."""

    async def main():
        import os

        blob = os.urandom(2 * PIECE)
        lying = Digest.from_bytes(b"not the blob")
        node = _node(tmp_path)
        await node.start()
        # Any re-read would explode: prove the rejection used the
        # streamed digest.
        orig = Digest.from_reader
        Digest.from_reader = classmethod(
            lambda cls, f: (_ for _ in ()).throw(AssertionError("re-read!"))
        )
        try:
            status, body = await _upload(node.addr, lying, [blob])
            assert status == 400, body
        finally:
            Digest.from_reader = orig
            await node.stop()

    asyncio.run(main())


def test_piece_length_tier_mismatch_falls_back(tmp_path):
    """A blob whose final size maps to a BIGGER piece-length tier than
    the stream-time bet: commit must discard the streamed piece hashes
    and run the windowed generate() pass at the right piece length."""

    async def main():
        import os

        table = PieceLengthConfig(table=((0, PIECE), (4 * PIECE, 2 * PIECE)))
        blob = os.urandom(6 * PIECE)  # lands in the 2*PIECE tier
        d = Digest.from_bytes(blob)
        node = _node(tmp_path, piece_lengths=table)
        await node.start()
        try:
            status, _ = await _upload(node.addr, d, [blob])
            assert status == 201
            mi = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            assert mi.piece_length == 2 * PIECE
            want = get_hasher("cpu").hash_pieces(blob, 2 * PIECE).tobytes()
            assert mi.serialize() == type(mi)(
                d, len(blob), 2 * PIECE, want
            ).serialize()
        finally:
            await node.stop()

    asyncio.run(main())


def test_fsync_durability_mode(tmp_path):
    """durability='fsync' commits blobs + sidecars with fsync on; the
    full upload->metainfo flow works and an invalid mode is rejected."""

    async def main():
        import os

        blob = os.urandom(2 * PIECE + 7)
        d = Digest.from_bytes(blob)
        node = _node(tmp_path, durability="fsync")
        await node.start()
        try:
            status, _ = await _upload(node.addr, d, [blob])
            assert status == 201
            assert node.store.read_cache_file(d) == blob
            assert node.store.get_metadata(d, TorrentMetaMetadata) is not None
        finally:
            await node.stop()

    asyncio.run(main())
    with pytest.raises(ValueError):
        from kraken_tpu.store import CAStore

        CAStore(str(tmp_path / "bad"), durability="paranoid")


def test_agent_pull_with_fsync_durability(tmp_path):
    """durability='fsync' on the AGENT: the whole-blob fsync at torrent
    completion runs off the event loop and the pull completes normally
    (the swarm path, not just the origin upload path)."""
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from test_swarm import FakeTracker, make_metainfo, make_peer, NS

    from kraken_tpu.p2p.scheduler import Scheduler

    async def main():
        import os

        from kraken_tpu.core.peer import PeerID
        from kraken_tpu.p2p.storage import (
            AgentTorrentArchive, BatchedVerifier,
        )
        from kraken_tpu.store import CAStore

        blob = os.urandom(300_000)
        mi = make_metainfo(blob, piece_length=16384)
        tracker = FakeTracker()
        tracker.metainfos[mi.digest.hex] = mi
        seeder, _ = make_peer(tmp_path, "seeder", tracker, seed_blob=blob)

        store = CAStore(str(tmp_path / "leech"), durability="fsync")
        ref: dict = {}
        client = tracker.client_for(ref)
        from kraken_tpu.p2p.scheduler import SchedulerConfig

        leecher = Scheduler(
            peer_id=PeerID(os.urandom(20).hex()),
            ip="127.0.0.1", port=0,
            archive=AgentTorrentArchive(store, BatchedVerifier()),
            metainfo_client=client, announce_client=client,
            config=SchedulerConfig(
                announce_interval_seconds=0.1,
                retry_tick_seconds=0.2,
            ),
        )
        ref["s"] = leecher
        await seeder.start()
        await leecher.start()
        try:
            seeder.seed(mi, NS)
            await asyncio.wait_for(leecher.download(NS, mi.digest), 15)
            assert store.read_cache_file(mi.digest) == blob
        finally:
            await seeder.stop()
            await leecher.stop()

    asyncio.run(main())


# -- pipelined ingest plane (core/ingest.py) -------------------------------


def _pipe_node(tmp_path, **kw):
    """Origin with the pipelined ingest plane on, windows kept small so a
    few hundred KiB of blob spans several windows."""
    kw.setdefault("ingest", {"window_bytes": 1 << 20, "windows_in_flight": 2})
    return _node(tmp_path, **kw)


def test_ingest_config_validation():
    """IngestConfig is the SIGHUP surface: unknown keys and out-of-range
    knobs must fail loudly at parse time, never half-apply."""
    from kraken_tpu.core.ingest import IngestConfig

    cfg = IngestConfig.from_dict(None)
    assert cfg.window_bytes == 64 << 20 and cfg.windows_in_flight == 2
    with pytest.raises(ValueError):
        IngestConfig(windows_in_flight=0)
    with pytest.raises(ValueError):
        IngestConfig(window_bytes=4096)


@pytest.mark.parametrize(
    "doc",
    [
        pytest.param({"widow_bytes": 1 << 20}, id="typo"),
        pytest.param({"pack_mode": "host"}, id="pack_mode"),
        pytest.param({"pack_workers": 1}, id="pack_workers"),
        pytest.param(
            {
                "window_bytes": 67108864, "windows_in_flight": 2,
                "pack_workers": 1, "pack_mode": "host",
                "resume": True, "serve_while_ingest": False,
            },
            id="section-shipped-before-PR31",
        ),
    ],
)
def test_ingest_config_rejects_unknown_keys(doc):
    """A key the section does not have -- a typo, or a knob of the packed
    route that left with PR 31 -- fails the parse (boot and SIGHUP alike);
    it is never silently accepted."""
    from kraken_tpu.core.ingest import IngestConfig

    with pytest.raises(ValueError, match="unknown ingest config keys"):
        IngestConfig.from_dict(doc)


@pytest.mark.parametrize(
    "rows,dispatched", [(1024, 1024), (2048, 2048), (1025, 2048)]
)
def test_whole_tile_window_takes_the_hashers_own_route(
    tile_kernel_shapes, rows, dispatched
):
    """A window of whole 1024-row tiles is no special case: through
    ``hasher: tpu`` (``use_pallas``) it is one dispatch of the
    natural-layout tile kernel, and a 1025th row rides the bucket
    ``hash_pieces`` pads it to (the kernel is the ``tile_kernel_shapes``
    stand-in)."""
    import numpy as np

    from kraken_tpu.core.ingest import IngestConfig, IngestPipeline
    from kraken_tpu.ops.sha256 import JaxPieceHasher

    plen = 1024
    pipe = IngestPipeline(
        JaxPieceHasher(use_pallas=True),
        IngestConfig(window_bytes=rows * plen),
    )
    blob = np.random.default_rng(rows).integers(
        0, 256, size=rows * plen, dtype=np.uint8
    ).tobytes()
    ses = pipe.session(plen)
    buf = ses.begin_window()
    assert len(buf) == len(blob)
    buf[:] = blob
    ses.submit(len(blob))
    got = ses.finish()
    assert [bytes(r) for r in got] == [
        hashlib.sha256(blob[i : i + plen]).digest()
        for i in range(0, len(blob), plen)
    ]
    assert tile_kernel_shapes == [(dispatched, plen)]
    assert set(ses.stage_seconds) == {"read", "transfer", "hash"}
    assert ses.stage_seconds["transfer"] == 0.0  # no stage_window here


def test_ingest_session_bit_identity():
    """The pipeline reorders WHEN pieces hash, never piece boundaries:
    digests must match the serial oracle for empty, single-window,
    multi-window, and ragged-tail blobs (the full edge square)."""
    import numpy as np

    from kraken_tpu.core.ingest import IngestConfig, IngestPipeline

    pipe = IngestPipeline(
        get_hasher("cpu"),
        IngestConfig(window_bytes=1 << 20, windows_in_flight=2),
    )
    plen = 4096
    rng = __import__("numpy").random.default_rng(7)
    for total in (0, plen, 3 * plen + 1, (1 << 20) * 2 + 5 * plen + 99):
        blob = rng.integers(0, 256, size=total, dtype=np.uint8).tobytes()
        ses = pipe.session(plen)
        off = 0
        while off < len(blob):
            buf = ses.begin_window()
            n = min(len(buf), len(blob) - off)
            buf[:n] = blob[off : off + n]
            off += n
            ses.submit(n)
        got = ses.finish()
        want = get_hasher("cpu").hash_pieces(blob, plen)
        assert np.array_equal(got, want), f"total={total}"
        if total:
            assert ses.windows >= 1 and ses.wall_seconds > 0


def test_pipelined_stream_matches_generate(tmp_path):
    """Uploads through a pipeline-enabled origin (cpu hasher): the
    stream-time window pass must produce a MetaInfo bit-identical to the
    serial oracle, across piece-misaligned chunk boundaries, multiple
    windows, and a short trailing piece -- and the stage metrics must
    move (the observability contract of the plane)."""

    async def main():
        import os

        from kraken_tpu.utils.metrics import REGISTRY

        blob = os.urandom((1 << 20) * 2 + 5 * PIECE + 1234)
        d = Digest.from_bytes(blob)
        node = _pipe_node(tmp_path)
        assert node.ingest_pipeline is not None
        assert node.generator.pipeline is node.ingest_pipeline
        windows_before = REGISTRY.counter(
            "ingest_windows_total", "x"
        ).value(hasher="cpu")
        await node.start()
        try:
            cuts = [0, PIECE // 3, (1 << 20) + 17, 2 * (1 << 20) - 1, len(blob)]
            chunks = [blob[a:b] for a, b in zip(cuts, cuts[1:])]
            status, _ = await _upload(node.addr, d, chunks)
            assert status == 201
            stored = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            want = get_hasher("cpu").hash_pieces(blob, PIECE).tobytes()
            assert stored.serialize() == type(stored)(
                d, len(blob), PIECE, want
            ).serialize()
            assert (
                REGISTRY.counter("ingest_windows_total", "x").value(
                    hasher="cpu"
                )
                > windows_before
            )
            assert "ingest_stage_seconds" in REGISTRY.render()
            # The re-generate path rides the pipeline too.
            node.store.delete_metadata(d, TorrentMetaMetadata)
            regen = node.generator.generate_sync(d)
            assert regen.serialize() == stored.serialize()
        finally:
            await node.stop()

    asyncio.run(main())


def test_pipelined_out_of_order_falls_back(tmp_path):
    """Out-of-order PATCHes on a pipeline origin: the tracker
    invalidates, the session aborts (leases back to the pool), and
    commit falls back to the verifying re-read -- which regenerates the
    same MetaInfo through the pipelined generate path."""

    async def main():
        import os

        blob = os.urandom((1 << 20) + 3 * PIECE + 7)
        d = Digest.from_bytes(blob)
        node = _pipe_node(tmp_path)
        await node.start()
        try:
            half = len(blob) // 2
            status, _ = await _upload(
                node.addr, d,
                [blob[half:], blob[:half]],
                offsets=[half, 0],  # second PATCH rewinds: invalidates
            )
            assert status == 201
            stored = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            want = get_hasher("cpu").hash_pieces(blob, PIECE).tobytes()
            assert stored.serialize() == type(stored)(
                d, len(blob), PIECE, want
            ).serialize()
        finally:
            await node.stop()

    asyncio.run(main())


def test_pipelined_tier_mismatch_falls_back(tmp_path):
    """Pipeline origin whose final size lands in a bigger piece-length
    tier than the stream-time bet: the streamed digests are at the wrong
    piece length, the session must be dropped, and the re-generate pass
    (pipelined, right tier) supplies the MetaInfo."""

    async def main():
        import os

        table = PieceLengthConfig(table=((0, PIECE), (4 * PIECE, 2 * PIECE)))
        blob = os.urandom(6 * PIECE)
        d = Digest.from_bytes(blob)
        node = _pipe_node(tmp_path, piece_lengths=table)
        await node.start()
        try:
            status, _ = await _upload(node.addr, d, [blob])
            assert status == 201
            mi = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            assert mi.piece_length == 2 * PIECE
            want = get_hasher("cpu").hash_pieces(blob, 2 * PIECE).tobytes()
            assert mi.serialize() == type(mi)(
                d, len(blob), 2 * PIECE, want
            ).serialize()
        finally:
            await node.stop()

    asyncio.run(main())


def test_pipelined_sharded_hasher_stream(tmp_path):
    """hasher=tpu-sharded + pipeline: stream-time piece hashing rides the
    sharded device plane (the virtual 8-device CPU mesh here) window by
    window; the MetaInfo must be bit-identical to the cpu oracle and the
    sharded hasher's gauges must move."""

    async def main():
        import os

        from kraken_tpu.utils.metrics import REGISTRY

        plen = 4096  # small pieces: short hash chains on the interpret mesh
        table = PieceLengthConfig(table=((0, plen),))
        blob = os.urandom((1 << 20) * 2 + 37 * plen + 123)
        d = Digest.from_bytes(blob)
        node = _pipe_node(tmp_path, hasher="tpu-sharded", piece_lengths=table)
        sharded_before = REGISTRY.counter(
            "hasher_bytes_total", "x"
        ).value(hasher="tpu-sharded")
        await node.start()
        try:
            status, _ = await _upload(node.addr, d, [blob])
            assert status == 201
            stored = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            want = get_hasher("cpu").hash_pieces(blob, plen).tobytes()
            assert stored.serialize() == type(stored)(
                d, len(blob), plen, want
            ).serialize()
            # The device plane did the stream-time piece pass.
            assert (
                REGISTRY.counter("hasher_bytes_total", "x").value(
                    hasher="tpu-sharded"
                )
                > sharded_before
            )
        finally:
            await node.stop()

    asyncio.run(main())


def test_ingest_reload_applies_and_live_enables(tmp_path):
    """SIGHUP semantics: knob changes live-apply to an existing
    pipeline, and an origin started WITHOUT `ingest:` grows the plane on
    reload (rollout step 1 of the OPERATIONS.md runbook)."""
    node = _pipe_node(tmp_path)
    assert node.ingest_pipeline.config.window_bytes == 1 << 20
    node.reload({"ingest": {"window_bytes": 2 << 20, "windows_in_flight": 3}})
    assert node.ingest_pipeline.config.window_bytes == 2 << 20
    assert node.ingest_pipeline.config.windows_in_flight == 3

    bare = _node(tmp_path / "bare")
    assert bare.ingest_pipeline is None
    bare.reload({"ingest": {"window_bytes": 4 << 20}})
    assert bare.ingest_pipeline is not None
    assert bare.generator.pipeline is bare.ingest_pipeline
    assert bare.ingest_pipeline.config.window_bytes == 4 << 20


# -- crash-safe resumable sessions (PR 17) ---------------------------------


def test_resume_adopts_journal_and_hashes_bit_identical(tmp_path):
    """Tentpole: a PATCH stream interrupted mid-upload resumes from the
    journaled durable offset and hashes BIT-IDENTICAL to an
    uninterrupted stream. The in-memory tracker is dropped between
    chunks (what an origin restart does to every tracker); HEAD must
    re-adopt from the journal+spool and the resumed tail must land on
    the stream fast path -- the committed MetaInfo equals the oracle."""

    async def main():
        import os

        blob = os.urandom(7 * PIECE + 321)
        d = Digest.from_bytes(blob)
        node = _pipe_node(tmp_path)
        await node.start()
        try:
            cut = 3 * PIECE + 100
            base = f"http://{node.addr}/namespace/ns/blobs/{d}"
            async with ClientSession() as http:
                async with http.post(f"{base}/uploads") as r:
                    uid = await r.text()
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[:cut],
                    headers={"X-Upload-Offset": "0"},
                ) as r:
                    assert r.status == 204
                # The journal landed with the flush.
                doc = node.store.read_upload_session(uid)
                assert doc is not None and doc["offset"] == cut
                assert doc["digest"] == d.hex
                # Simulate restart: the tracker (and its pipeline
                # session) evaporates; only spool+journal survive.
                node.server._upload_digests.pop(uid).invalidate()
                async with http.request(
                    "HEAD", f"{base}/uploads/{uid}"
                ) as r:
                    assert r.status == 200
                    assert int(r.headers["X-Upload-Offset"]) == cut
                # Adopted: the tracker is live again and mid-stream.
                assert uid in node.server._upload_digests
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[cut:],
                    headers={"X-Upload-Offset": str(cut)},
                ) as r:
                    assert r.status == 204
                async with http.put(f"{base}/uploads/{uid}/commit") as r:
                    assert r.status == 201
            stored = node.store.get_metadata(d, TorrentMetaMetadata).metainfo
            want = get_hasher("cpu").hash_pieces(blob, PIECE).tobytes()
            assert stored.serialize() == type(stored)(
                d, len(blob), PIECE, want
            ).serialize()
            assert node.store.read_cache_file(d) == blob
            # Commit cleaned the journal up with the spool.
            assert node.store.read_upload_session(uid) is None
        finally:
            await node.stop()

    asyncio.run(main())


def test_resume_patch_past_durable_size_409s(tmp_path):
    """A blind PATCH retry past the journaled durable size would seek
    past EOF and bury a hole under the client's bytes -- the origin must
    409 it (the resume protocol's signal to HEAD for the real offset),
    while rewrites at/below the durable size stay allowed."""

    async def main():
        import os

        blob = os.urandom(4 * PIECE)
        d = Digest.from_bytes(blob)
        node = _node(tmp_path)
        await node.start()
        try:
            base = f"http://{node.addr}/namespace/ns/blobs/{d}"
            async with ClientSession() as http:
                async with http.post(f"{base}/uploads") as r:
                    uid = await r.text()
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[:PIECE],
                    headers={"X-Upload-Offset": "0"},
                ) as r:
                    assert r.status == 204
                # Past-EOF offset (the crash-retry hole): refused.
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[2 * PIECE :],
                    headers={"X-Upload-Offset": str(2 * PIECE)},
                ) as r:
                    assert r.status == 409
                # Recover exactly as a resuming client would.
                async with http.request(
                    "HEAD", f"{base}/uploads/{uid}"
                ) as r:
                    off = int(r.headers["X-Upload-Offset"])
                assert off == PIECE
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[off:],
                    headers={"X-Upload-Offset": str(off)},
                ) as r:
                    assert r.status == 204
                async with http.put(f"{base}/uploads/{uid}/commit") as r:
                    assert r.status == 201
            assert node.store.read_cache_file(d) == blob
        finally:
            await node.stop()

    asyncio.run(main())


def test_unadoptable_session_404s_and_client_restarts(tmp_path):
    """A session whose spool contradicts its journal (here: forced via
    the origin.upload.resume failpoint) must 404 the HEAD -- the
    client's cue to restart the upload from scratch -- and the suspect
    spool+journal must be gone."""

    async def main():
        import os

        from kraken_tpu.utils import failpoints

        blob = os.urandom(3 * PIECE)
        d = Digest.from_bytes(blob)
        node = _node(tmp_path)
        await node.start()
        try:
            base = f"http://{node.addr}/namespace/ns/blobs/{d}"
            async with ClientSession() as http:
                async with http.post(f"{base}/uploads") as r:
                    uid = await r.text()
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob[:PIECE],
                    headers={"X-Upload-Offset": "0"},
                ) as r:
                    assert r.status == 204
                node.server._upload_digests.pop(uid).invalidate()
                failpoints.allow()
                failpoints.FAILPOINTS.arm("origin.upload.resume", "once")
                try:
                    async with http.request(
                        "HEAD", f"{base}/uploads/{uid}"
                    ) as r:
                        assert r.status == 404
                finally:
                    failpoints.FAILPOINTS.disarm_all()
                    failpoints.allow(False)
                # The whole session is discarded: spool AND journal.
                assert node.store.read_upload_session(uid) is None
                import os as _os

                assert not _os.path.exists(node.store.upload_path(uid))
        finally:
            await node.stop()

    asyncio.run(main())


def test_pipeline_abort_returns_every_lease(tmp_path):
    """abort() mid-stream must provably return every BufferPool lease --
    a leaked staging lease caps all future ingest concurrency."""
    from kraken_tpu.core.ingest import IngestConfig, IngestPipeline

    pipe = IngestPipeline(
        get_hasher("cpu"),
        IngestConfig(window_bytes=1 << 20, windows_in_flight=2),
    )
    ses = pipe.session(4096)
    buf = ses.begin_window()
    buf[: 4096] = b"x" * 4096
    ses.submit(4096)
    ses.begin_window()  # second window leased, never submitted
    ses.abort()
    assert pipe._bufpool.leased == 0


def test_ten_concurrent_small_sessions_on_shipped_windows():
    """The small-push shape (ten pushers, one window each, 1 KiB-1 MiB)
    on the shipped window: three buffers are retained, so seven of ten
    concurrent first windows miss, each asking for a whole 64 MiB class
    to hold <= 1 MiB. Digests are hashlib's, and the pool's leak audit
    holds: nothing leased, nothing retained past the budget."""
    import threading

    import numpy as np

    from kraken_tpu.configutil import load_config
    from kraken_tpu.core.ingest import IngestConfig, IngestPipeline
    from kraken_tpu.utils.metrics import REGISTRY

    cfg = IngestConfig.from_dict(load_config("config/origin/base.yaml")["ingest"])
    assert (cfg.window_bytes, cfg.windows_in_flight) == (64 << 20, 2)
    pipe = IngestPipeline(get_hasher("cpu"), cfg)
    pool = pipe._bufpool
    plen = 256 * 1024
    rng = np.random.default_rng(29)
    sizes = [int(s) for s in np.geomspace(1 << 10, 1 << 20, 10)]
    blobs = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes() for n in sizes]
    all_leased = threading.Barrier(len(blobs))
    got: list = [None] * len(blobs)

    def push(i: int) -> None:
        ses = pipe.session(plen)
        buf = ses.begin_window()
        assert len(buf) == cfg.window_bytes
        all_leased.wait(timeout=30)  # ten windows out at once
        buf[: len(blobs[i])] = blobs[i]
        ses.submit(len(blobs[i]))
        got[i] = ses.finish()

    threads = [threading.Thread(target=push, args=(i,)) for i in range(len(blobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)

    for blob, digests in zip(blobs, got):
        want = [
            hashlib.sha256(blob[o : o + plen]).digest()
            for o in range(0, len(blob), plen)
        ]
        assert [bytes(d) for d in digests] == want
    budget = cfg.window_bytes * (cfg.windows_in_flight + 1)
    assert pool.leased == 0
    assert REGISTRY.gauge("bufpool_leased").value(pool="ingest") == 0
    assert 0 < pool.retained_bytes <= budget
    assert pool.misses == len(blobs) and pool.hits == 0
    assert pool.miss_bytes == len(blobs) * cfg.window_bytes
    # Another lap is served from what was kept.
    ses = pipe.session(plen)
    ses.begin_window()
    ses.abort()
    assert pool.hits == 1 and pool.leased == 0


def test_upload_digest_ttl_purge_and_capacity_eviction(tmp_path):
    """Satellite (b): idle trackers purge on the TTL tick (not only past
    a size watermark) and the hard cap evicts the OLDEST idle tracker,
    metered -- never a silent drop."""

    async def main():
        from kraken_tpu.utils.metrics import REGISTRY

        node = _node(tmp_path)
        await node.start()
        try:
            server = node.server
            base = f"http://{node.addr}/namespace/ns/blobs"
            d = Digest.from_bytes(b"ttl-purge")
            async with ClientSession() as http:
                async with http.post(f"{base}/{d}/uploads") as r:
                    uid = await r.text()
            assert uid in server._upload_digests
            # Age the tracker past the TTL and tick the purge.
            server._upload_digests[uid].created -= (
                server.UPLOAD_DIGEST_TTL_SECONDS + 1
            )
            before = REGISTRY.counter(
                "upload_digests_evicted_total"
            ).value(reason="ttl")
            server.purge_upload_digests()
            assert uid not in server._upload_digests
            after = REGISTRY.counter(
                "upload_digests_evicted_total"
            ).value(reason="ttl")
            assert after == before + 1

            # Capacity: with the cap forced to 1, a second start evicts
            # the first (oldest) tracker with reason=capacity.
            server.UPLOAD_DIGEST_CAP = 1
            async with ClientSession() as http:
                async with http.post(f"{base}/{d}/uploads") as r:
                    uid1 = await r.text()
                cap_before = REGISTRY.counter(
                    "upload_digests_evicted_total"
                ).value(reason="capacity")
                async with http.post(f"{base}/{d}/uploads") as r:
                    uid2 = await r.text()
            assert uid1 not in server._upload_digests
            assert uid2 in server._upload_digests
            cap_after = REGISTRY.counter(
                "upload_digests_evicted_total"
            ).value(reason="capacity")
            assert cap_after == cap_before + 1
        finally:
            await node.stop()

    asyncio.run(main())
