"""Golden tests for the JAX SHA-256 plane vs hashlib (exact equality --
crypto hashes admit no tolerance). SURVEY.md SS4 tier 5."""

import hashlib
import os

import numpy as np
import pytest

from kraken_tpu.core.hasher import get_hasher


def ref_pieces(data: bytes, piece_length: int) -> np.ndarray:
    return get_hasher("cpu").hash_pieces(data, piece_length)


@pytest.fixture(scope="module")
def tpu_hasher():
    return get_hasher("tpu")


# -- hash_batch: single messages of every tricky length ---------------------

@pytest.mark.parametrize(
    "length",
    [0, 1, 3, 55, 56, 57, 63, 64, 65, 119, 120, 127, 128, 1000, 4096, 65537],
)
def test_single_message_lengths(tpu_hasher, length):
    data = os.urandom(length)
    got = tpu_hasher.hash_batch([data])
    assert got.shape == (1, 32)
    assert bytes(got[0]) == hashlib.sha256(data).digest()


def test_known_vectors(tpu_hasher):
    # FIPS 180-2 test vectors.
    cases = {
        b"abc": "ba7816bf8f01cfea414140de5dae2223b00361a396177a9cb410ff61f20015ad",
        b"": "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855",
        b"abcdbcdecdefdefgefghfghighijhijkijkljklmklmnlmnomnopnopq":
            "248d6a61d20638b8e5c026930c3e6039a33ce45964ff2167f6ecedd419db06c1",
    }
    got = tpu_hasher.hash_batch(list(cases))
    for row, expect in zip(got, cases.values()):
        assert bytes(row).hex() == expect


def test_ragged_batch(tpu_hasher):
    rng = np.random.default_rng(0)
    pieces = [rng.integers(0, 256, size=int(n), dtype=np.uint8).tobytes()
              for n in rng.integers(0, 3000, size=40)]
    got = tpu_hasher.hash_batch(pieces)
    for row, p in zip(got, pieces):
        assert bytes(row) == hashlib.sha256(p).digest()


def test_empty_batch(tpu_hasher):
    assert tpu_hasher.hash_batch([]).shape == (0, 32)


# -- hash_pieces: blob splitting, uniform fast path, ragged tail ------------

@pytest.mark.parametrize(
    "blob_len,piece_len",
    [
        (0, 64),            # empty blob -> zero pieces
        (64, 64),           # exactly one piece
        (640, 64),          # uniform, multiple of 64 (fast path)
        (650, 64),          # fast path + short tail
        (1 << 20, 1 << 16), # 1 MiB blob, 64 KiB pieces
        ((1 << 20) + 12345, 1 << 16),
        (1000, 100),        # piece length not a multiple of 64 (ragged path)
        (37, 100),          # single short piece
    ],
)
def test_hash_pieces_matches_cpu(tpu_hasher, blob_len, piece_len):
    data = os.urandom(blob_len)
    got = tpu_hasher.hash_pieces(data, piece_len)
    want = ref_pieces(data, piece_len)
    assert got.shape == want.shape
    assert np.array_equal(got, want)


def test_hash_pieces_streams_sub_batches():
    # Force multiple device dispatches with a tiny sub-batch budget.
    from kraken_tpu.ops.sha256 import JaxPieceHasher

    h = JaxPieceHasher(sub_batch_bytes=256)
    data = os.urandom(64 * 40 + 17)
    got = h.hash_pieces(data, 64)
    assert np.array_equal(got, ref_pieces(data, 64))
    got2 = h.hash_batch([data[i * 100 : (i + 1) * 100] for i in range(20)])
    for row, i in zip(got2, range(20)):
        assert bytes(row) == hashlib.sha256(data[i * 100 : (i + 1) * 100]).digest()


def test_matches_cpu_hasher_interface():
    cpu = get_hasher("cpu")
    tpu = get_hasher("tpu")
    data = os.urandom(300000)
    assert np.array_equal(
        cpu.hash_pieces(data, 1 << 16), tpu.hash_pieces(data, 1 << 16)
    )


def test_hash_batch_mixed_sizes_bounded_memory():
    """One large piece among many tiny ones must not blow up the padded
    allocation (regression: group sizing must respect sub_batch_bytes)."""
    from kraken_tpu.ops.sha256 import JaxPieceHasher

    h = JaxPieceHasher(sub_batch_bytes=1 << 20)
    pieces = [os.urandom(40) for _ in range(300)] + [os.urandom(700_000)]
    got = h.hash_batch(pieces)
    for row, p in zip(got, pieces):
        assert bytes(row) == hashlib.sha256(p).digest()


@pytest.mark.parametrize(
    "sub_batch_pieces,want_shapes",
    [(64, [(1, 8192), (16, 4096)]), (4, [(1, 4096), (1, 8192), (4, 4096)])],
)
def test_hash_batch_sends_piece_sized_uniform_groups_to_the_tile_kernel(
    monkeypatch, sub_batch_pieces, want_shapes
):
    """On an accelerator (``use_pallas``) the equal-length, piece-sized
    entries of a hash_batch -- an agent's verify batch -- go through the
    tile kernel in rows bucketed to powers of four and bounded by the
    sub-batch budget; everything else stays on the ragged scan, and every
    digest lands on its own row. The kernel itself only runs on the chip
    (chip_smoke.py holds it to hashlib there); a stand-in with the same
    contract records what it was handed."""
    import jax.numpy as jnp

    from kraken_tpu.ops import sha256 as plane
    from kraken_tpu.ops import sha256_pallas

    shapes = []

    def tile_kernel(data_u8, piece_length, interpret=None):
        shapes.append(tuple(data_u8.shape))
        pad = jnp.asarray(plane._pad_block_for(piece_length))
        return plane._sha256_uniform(data_u8, pad, piece_length // 64)

    monkeypatch.setattr(sha256_pallas, "hash_pieces_device", tile_kernel)
    monkeypatch.setattr(plane, "_TILE_KERNEL_MIN_BYTES", 4096)
    h = plane.JaxPieceHasher(
        use_pallas=True, sub_batch_bytes=sub_batch_pieces * 4096
    )
    pieces = [
        os.urandom(100), os.urandom(4096), os.urandom(4096), os.urandom(4097),
        os.urandom(8192), os.urandom(4096), os.urandom(4096 - 64),
        os.urandom(4096), os.urandom(4096), b"",
    ]
    got = h.hash_batch(pieces)
    for row, p in zip(got, pieces):
        assert bytes(row) == hashlib.sha256(p).digest()
    assert sorted(shapes) == want_shapes


@pytest.mark.skipif(
    not os.environ.get("RUN_PALLAS_INTERPRET"),
    reason="interpret-mode kernel execution takes minutes on CPU; the "
    "kernel is golden-tested on real TPU (set RUN_PALLAS_INTERPRET=1)",
)
def test_pallas_kernel_interpret_mode():
    """The Pallas kernel (interpret mode on CPU) matches hashlib, including
    block-group padding (chains not a multiple of the kernel's _KB)."""
    import jax.numpy as jnp

    from kraken_tpu.ops.sha256_pallas import hash_pieces_device

    for pl_len, n in ((64, 3), (576, 5), (1024, 2)):
        data = np.frombuffer(os.urandom(n * pl_len), dtype=np.uint8).reshape(n, pl_len)
        out = hash_pieces_device(jnp.asarray(data), pl_len)
        from kraken_tpu.ops.sha256 import _digest_bytes

        got = _digest_bytes(out)
        for i in range(n):
            assert bytes(got[i]) == hashlib.sha256(data[i].tobytes()).digest()
