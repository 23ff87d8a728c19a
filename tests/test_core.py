"""Unit tests for kraken_tpu.core (digest, metainfo, peer, hasher)."""

import hashlib
import io
import os

import numpy as np
import pytest

from kraken_tpu.core import (
    BlobInfo,
    CPUPieceHasher,
    Digest,
    DigestError,
    Digester,
    MetaInfo,
    MetaInfoError,
    PeerID,
    PeerIDFactory,
    PeerInfo,
    get_hasher,
)
from kraken_tpu.core.fixtures import (
    blob_and_metainfo_fixture,
    blob_fixture,
    metainfo_fixture,
)
from kraken_tpu.core.metainfo import num_pieces


class TestDigest:
    def test_from_bytes_matches_hashlib(self):
        data = b"hello kraken"
        d = Digest.from_bytes(data)
        assert d.hex == hashlib.sha256(data).hexdigest()
        assert str(d) == f"sha256:{d.hex}"
        assert d.raw == hashlib.sha256(data).digest()

    def test_parse_roundtrip(self):
        d = Digest.from_bytes(b"x")
        assert Digest.parse(str(d)) == d

    @pytest.mark.parametrize(
        "bad",
        [
            "sha256",  # no separator
            "md5:" + "a" * 32,  # wrong algo
            "sha256:" + "a" * 63,  # short hex
            "sha256:" + "A" * 64,  # uppercase rejected (canonical form only)
            "sha256:" + "g" * 64,  # non-hex
        ],
    )
    def test_parse_rejects_malformed(self, bad):
        with pytest.raises(DigestError):
            Digest.parse(bad)

    def test_from_reader_streams(self):
        data = blob_fixture(10 * 1024 * 1024 + 13, seed=1)
        assert Digest.from_reader(io.BytesIO(data)) == Digest.from_bytes(data)

    def test_digester_incremental(self):
        d = Digester()
        d.update(b"hello ")
        d.update(b"world")
        assert d.digest() == Digest.from_bytes(b"hello world")

    def test_digester_tee(self):
        d = Digester()
        chunks = [b"ab", b"cd", b"ef"]
        out = list(d.tee(iter(chunks)))
        assert out == chunks
        assert d.digest() == Digest.from_bytes(b"abcdef")

    def test_hashable_and_ordered(self):
        a, b = Digest.from_bytes(b"a"), Digest.from_bytes(b"b")
        assert len({a, b, Digest.from_bytes(b"a")}) == 2
        assert (a < b) != (b < a)


class TestMetaInfo:
    def test_num_pieces(self):
        assert num_pieces(0, 4) == 0
        assert num_pieces(1, 4) == 1
        assert num_pieces(4, 4) == 1
        assert num_pieces(5, 4) == 2

    def test_piece_layout_with_ragged_tail(self):
        blob = blob_fixture(10_000, seed=2)
        mi = metainfo_fixture(blob, piece_length=4096)
        assert mi.num_pieces == 3
        assert mi.piece_length_of(0) == 4096
        assert mi.piece_length_of(2) == 10_000 - 2 * 4096
        with pytest.raises(IndexError):
            mi.piece_length_of(3)

    def test_verify_piece(self):
        blob, mi = blob_and_metainfo_fixture(size=10_000, piece_length=4096, seed=3)
        for i in range(mi.num_pieces):
            piece = blob[i * 4096 : (i + 1) * 4096]
            assert mi.verify_piece(i, piece)
            assert not mi.verify_piece(i, piece[:-1])  # wrong length
            if piece:
                corrupted = bytes([piece[0] ^ 1]) + piece[1:]
                assert not mi.verify_piece(i, corrupted)

    def test_serialize_roundtrip(self):
        _, mi = blob_and_metainfo_fixture(seed=4)
        mi2 = MetaInfo.deserialize(mi.serialize())
        assert mi2 == mi
        assert mi2.info_hash == mi.info_hash

    def test_info_hash_depends_on_content(self):
        blob = blob_fixture(8192, seed=5)
        a = metainfo_fixture(blob, piece_length=4096)
        b = metainfo_fixture(blob, piece_length=8192)
        assert a.info_hash != b.info_hash

    def test_deserialize_rejects_garbage(self):
        with pytest.raises(MetaInfoError):
            MetaInfo.deserialize(b"not json")
        with pytest.raises(MetaInfoError):
            MetaInfo.deserialize(b'{"version": 99}')

    def test_hash_count_validated(self):
        blob = blob_fixture(8192, seed=6)
        with pytest.raises(MetaInfoError):
            MetaInfo(Digest.from_bytes(blob), len(blob), 4096, b"\x00" * 32)

    def test_zero_length_blob(self):
        mi = metainfo_fixture(b"", piece_length=4096)
        assert mi.num_pieces == 0
        assert MetaInfo.deserialize(mi.serialize()) == mi


class TestPeer:
    def test_addr_hash_deterministic(self):
        f = PeerIDFactory(PeerIDFactory.ADDR_HASH)
        assert f.create("10.0.0.1", 5000) == f.create("10.0.0.1", 5000)
        assert f.create("10.0.0.1", 5000) != f.create("10.0.0.1", 5001)

    def test_random_unique(self):
        f = PeerIDFactory(PeerIDFactory.RANDOM)
        assert f.create("10.0.0.1", 5000) != f.create("10.0.0.1", 5000)

    def test_peer_info_roundtrip(self):
        p = PeerInfo(PeerID("ab" * 20), "10.0.0.2", 1234, origin=True, complete=True)
        assert PeerInfo.from_dict(p.to_dict()) == p
        assert p.addr == "10.0.0.2:1234"

    def test_blob_info_roundtrip(self):
        assert BlobInfo.from_dict(BlobInfo(123).to_dict()) == BlobInfo(123)


class TestCPUPieceHasher:
    def test_matches_hashlib_ragged(self):
        h = CPUPieceHasher()
        blob = blob_fixture(10_000, seed=7)
        out = h.hash_pieces(blob, 4096)
        assert out.shape == (3, 32)
        for i in range(3):
            piece = blob[i * 4096 : (i + 1) * 4096]
            assert out[i].tobytes() == hashlib.sha256(piece).digest()

    def test_empty_blob(self):
        assert CPUPieceHasher().hash_pieces(b"", 4096).shape == (0, 32)

    def test_hash_batch(self):
        h = CPUPieceHasher()
        pieces = [b"a", b"bb", b"", blob_fixture(5000, seed=8)]
        out = h.hash_batch(pieces)
        assert out.shape == (4, 32)
        for i, p in enumerate(pieces):
            assert out[i].tobytes() == hashlib.sha256(p).digest()

    def test_registry(self):
        assert isinstance(get_hasher("cpu"), CPUPieceHasher)
        assert get_hasher("cpu") is get_hasher("cpu")
        with pytest.raises(KeyError):
            get_hasher("nope")


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_is_placed_from_outside(monkeypatch, env_dir):
    """Before the first device hasher is built, JAX's persistent compile
    cache gets ONE fixed home beside the package -- unless
    JAX_COMPILATION_CACHE_DIR is set, and then the program sets nothing."""
    import jax

    from kraken_tpu.core import hasher as hasher_mod

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        hasher_mod._place_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache") if env_dir is None else None
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_device_hashers_report_their_devices():
    """What a component prints as `hasher_devices` on its READY line:
    None for the host hasher, the default device for `tpu`."""
    assert get_hasher("cpu").device_info() is None
    assert get_hasher("tpu").device_info() == {
        "platform": "cpu", "device_kind": "cpu", "count": 1,
    }


class TestPooledCPUPieceHasher:
    """hash_workers pool: bit-identical to the serial oracle -- sharding
    only reorders WHICH thread hashes a piece, never piece boundaries --
    and visible on the pool gauges."""

    def test_hash_pieces_parity_with_serial(self):
        blob = blob_fixture(1_000_000, seed=11)
        serial = CPUPieceHasher().hash_pieces(blob, 4096)
        for workers in (1, 2, 3):
            pooled = CPUPieceHasher(workers=workers).hash_pieces(blob, 4096)
            assert (pooled == serial).all(), workers

    def test_hash_pieces_parity_ragged_and_tiny(self):
        h = CPUPieceHasher(workers=2)
        for size in (0, 1, 4095, 4096, 4097, 40_961):
            blob = blob_fixture(size, seed=size) if size else b""
            assert (
                h.hash_pieces(blob, 4096)
                == CPUPieceHasher().hash_pieces(blob, 4096)
            ).all(), size

    def test_hash_batch_parity(self):
        pieces = [b"", b"x", blob_fixture(5000, seed=1),
                  blob_fixture(100_000, seed=2)]
        serial = CPUPieceHasher().hash_batch(pieces)
        pooled = CPUPieceHasher(workers=2).hash_batch(pieces)
        assert (pooled == serial).all()

    def test_registry_caches_per_worker_count(self):
        assert get_hasher("cpu", workers=2) is get_hasher("cpu", workers=2)
        assert get_hasher("cpu", workers=2) is not get_hasher("cpu")
        assert get_hasher("cpu").pool is None
        assert get_hasher("cpu", workers=2).pool.workers == 2

    def test_pool_gauges_visible(self):
        from kraken_tpu.utils.metrics import REGISTRY

        CPUPieceHasher(workers=2).hash_pieces(blob_fixture(100_000, seed=3),
                                              4096)
        text = REGISTRY.render()
        # Label carries the worker count: two pools in one process must
        # publish to distinct series.
        assert 'hash_pool_workers{pool="cpu/2"} 2' in text
        assert "hash_pool_occupancy" in text
        assert "hash_pool_queue_depth" in text


def test_metainfo_deserialize_fuzz_only_metainfoerror():
    """Metainfo comes off the wire (tracker proxy): any corruption --
    structural or bit-level -- must surface as MetaInfoError, never a raw
    KeyError/AttributeError escaping to the scheduler."""
    import json



    rng = np.random.default_rng(5)
    blob = b"x" * 1000
    mi = MetaInfo(
        Digest.from_bytes(blob), len(blob), 1024,
        __import__("hashlib").sha256(blob).digest(),
    )
    raw = mi.serialize()
    doc = json.loads(raw)
    cases = [
        b"", b"null", b"[]", b'"x"', b"{}", b'{"version":1}',
        b'{"version":1,"info":[]}', b'{"version":1,"info":{}}',
        json.dumps({**doc, "info": {
            k: v for k, v in doc["info"].items() if k != "name"
        }}).encode(),
        json.dumps({**doc, "digest": 5}).encode(),
        json.dumps({**doc, "info": {**doc["info"], "piece_hashes": "zz"}}).encode(),
        json.dumps({**doc, "info": {**doc["info"], "length": "big"}}).encode(),
    ]
    for _ in range(300):
        b = bytearray(raw)
        i = int(rng.integers(0, len(b)))
        b[i] ^= int(rng.integers(1, 256))
        cases.append(bytes(b))
    for c in cases:
        try:
            got = MetaInfo.deserialize(c)
            assert got.digest == mi.digest  # survived mutation unchanged
        except MetaInfoError:
            pass  # the only acceptable failure type
