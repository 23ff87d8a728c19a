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
    monkeypatch, tile_kernels, tile_kernel_shapes, sub_batch_pieces,
    want_shapes,
):
    """On an accelerator (``use_pallas``) the equal-length, piece-sized
    entries of a hash_batch -- an agent's verify batch -- go through the
    tile kernel in rows bucketed to powers of four and bounded by the
    sub-batch budget; everything else goes to the ragged tile kernel, and
    every digest lands on its own row (the uniform kernel is the
    ``tile_kernel_shapes`` stand-in)."""
    from kraken_tpu.ops import sha256 as plane

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
    assert sorted(tile_kernel_shapes) == want_shapes


# -- the two tile kernels (interpret mode) ----------------------------------

def rolled_rounds(state, wget):
    """``sha256_pallas._rounds64`` as four passes of a sixteen-round loop.

    XLA:CPU does not finish compiling the unrolled 64 rounds in minutes
    (ops/sha256.py ``_UNROLL``), so the interpret-mode tests of the tile
    kernels trace this in their place. The unrolled rounds are the ones
    sha256_tiles has always had, held to hashlib on the chip; what these
    tests hold is everything the kernels put around them: the relayout,
    the rolled block loop, the padding block folded from constants, the
    per-lane block counts, the skipped groups, the state carried from
    call to call."""
    import jax
    import jax.numpy as jnp

    from kraken_tpu.ops.sha256_pallas import _K, _rotr

    def sixteen(q, carry):
        a, b, c, d, e, f, g, h = carry[:8]
        w = list(carry[8:])
        for i in range(16):
            k = jnp.uint32(_K[i])
            for p in (1, 2, 3):
                k = jnp.where(q == p, jnp.uint32(_K[16 * p + i]), k)
            wi = w[i]
            s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
            t1 = h + s1 + (g ^ (e & (f ^ g))) + k + wi
            s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
            maj = (a & (b ^ c)) ^ (b & c)
            a, b, c, d, e, f, g, h = t1 + s0 + maj, a, b, c, d + t1, e, f, g
            w15, w2 = w[(i + 1) % 16], w[(i + 14) % 16]
            e0 = _rotr(w15, 7) ^ _rotr(w15, 18) ^ (w15 >> np.uint32(3))
            e1 = _rotr(w2, 17) ^ _rotr(w2, 19) ^ (w2 >> np.uint32(10))
            w[i] = wi + e0 + w[(i + 9) % 16] + e1
        return (a, b, c, d, e, f, g, h, *w)

    out = jax.lax.fori_loop(
        0, 4, sixteen, (*state, *[wget(j) for j in range(16)])
    )
    return [s + v for s, v in zip(state, out[:8])]


@pytest.fixture(scope="module")
def tile_kernels():
    """``sha256_pallas`` with both tile kernels runnable on the CPU."""
    from kraken_tpu.ops import sha256_pallas

    jitted = (sha256_pallas.sha256_ragged_slab, sha256_pallas.sha256_tiles)
    real = sha256_pallas._rounds64
    sha256_pallas._rounds64 = rolled_rounds
    for fn in jitted:
        fn.clear_cache()
    yield sha256_pallas
    sha256_pallas._rounds64 = real
    for fn in jitted:
        fn.clear_cache()


def _ragged_digests(kernel, msgs, shape):
    from kraken_tpu.core.hasher import sha_blocks
    from kraken_tpu.ops.sha256 import _digest_bytes, _round_up, _sha_pad_np

    nblocks = np.array([sha_blocks(len(m)) for m in msgs], dtype=np.int32)
    axis = _round_up(int(nblocks.max()), shape[1])
    rows = np.stack(
        [_sha_pad_np(memoryview(m), axis).reshape(-1) for m in msgs]
    )
    return _digest_bytes(kernel.sha256_ragged_tiles(rows, nblocks, shape))


_SLAB = 16  # blocks a call in these tests: 1 KiB of a row
_SLAB_BYTES = _SLAB * 64


@pytest.mark.parametrize(
    "length",
    [
        0, 1, 55, 56, 63, 64, 65, 119, 120, 1024,
        pytest.param(_SLAB_BYTES - 64 - 9, id="one-slab-less-a-block"),
        pytest.param(_SLAB_BYTES - 9, id="one-slab"),
        pytest.param(_SLAB_BYTES - 8, id="one-slab-and-a-block"),
        pytest.param(3 * _SLAB_BYTES + 7, id="three-slabs-and-7-bytes"),
    ],
)
def test_ragged_tile_kernel_lengths(tile_kernels, length):
    """One row, one lane: every padding edge, and chains of one, two and
    four calls whose state is carried from each call into the next."""
    data = os.urandom(length)
    got = _ragged_digests(tile_kernels, [data], (1, _SLAB))
    assert bytes(got[0]) == hashlib.sha256(data).digest()


@pytest.mark.parametrize("lanes", [1024, 8])
def test_ragged_tile_kernel_rows_ending_in_different_slabs(
    tile_kernels, lanes
):
    """Rows of one batch end in the first, second, third and fourth call:
    a lane past its own count keeps its state through the later calls,
    and lanes without a row are never read."""
    lengths = [0, 100, _SLAB_BYTES - 9, _SLAB_BYTES, 2 * _SLAB_BYTES + 5,
               3 * _SLAB_BYTES + 7, 700]
    msgs = [os.urandom(n) for n in lengths]
    got = _ragged_digests(tile_kernels, msgs, (lanes, _SLAB))
    for row, m in zip(got, msgs):
        assert bytes(row) == hashlib.sha256(m).digest()


@pytest.mark.parametrize("use_pallas", [True, False])
def test_hash_batch_sends_short_and_odd_rows_to_the_ragged_tile_kernel(
    tile_kernels, use_pallas
):
    """On an accelerator (``use_pallas``) whatever the uniform tile kernel
    does not take -- short rows, odd lengths -- goes through the ragged
    tile kernel at its two shipped shapes (rows enough to fill a tile's
    copy as one tile, an outlier or a few rows each as a chain of its
    own) and the XLA scan books nothing; on the CPU backend it is the
    reverse."""
    from test_device_ledger import counts, delta

    from kraken_tpu.ops import sha256 as plane
    from kraken_tpu.utils.metrics import REGISTRY

    tiles = {"purpose": "verify", "kernel": "sha256_ragged_tiles"}
    scan = {"purpose": "verify", "kernel": "sha256_ragged"}
    before = counts(REGISTRY, **tiles), counts(REGISTRY, **scan)
    h = plane.JaxPieceHasher(use_pallas=use_pallas)
    rng = np.random.default_rng(7)
    run = [os.urandom(int(n)) for n in rng.integers(6000, 9000, size=40)]
    few = [os.urandom(70_001), b"", os.urandom(4097)]
    for pieces in (run + few[:1], few[1:]):
        got = h.hash_batch(pieces)
        for row, p in zip(got, pieces):
            assert bytes(row) == hashlib.sha256(p).digest()
    d_tiles = delta(before[0], counts(REGISTRY, **tiles))
    d_scan = delta(before[1], counts(REGISTRY, **scan))
    useful = sum(plane.sha_blocks(len(p)) for p in run + few)
    if use_pallas:
        assert d_scan["sections"] == 0
        # The long row first and alone, one tile for the run of 40 alike,
        # and the batch of two row by row.
        assert d_tiles["sections"] == 4 and d_tiles["rows"] == 1024 + 3
        assert d_tiles["useful_blocks"] == useful
        row_axes = sum(
            -(-plane.sha_blocks(len(p)) // 512) * 512 for p in few
        )
        tile_axis = -(-max(plane.sha_blocks(len(p)) for p in run) // 64) * 64
        assert d_tiles["blocks"] == 1024 * tile_axis + row_axes
        assert d_tiles["first_use"] <= 2  # the compiled shapes, not lengths
    else:
        assert d_tiles["sections"] == 0
        assert d_scan["sections"] == 2 and d_scan["useful_blocks"] == useful


@pytest.mark.parametrize(
    "rows,blocks",
    [
        pytest.param(3, 1, id="1-block"),
        pytest.param(2, 7, id="7-blocks-one-short-group"),
        pytest.param(5, 8, id="8-blocks-one-full-group"),
        pytest.param(5, 9, id="9-blocks"),
        pytest.param(2, 16, id="16-blocks-two-full-groups"),
        pytest.param(1, 17, id="17-blocks"),
        pytest.param(1, 8, id="one-row"),
        pytest.param(1025, 1, id="1025-rows-two-tiles"),
        pytest.param(1025, 9, id="1025-rows-9-blocks"),
    ],
)
def test_uniform_tile_kernel_chains_and_rows(tile_kernels, rows, blocks):
    """``sha256_tiles`` in interpret mode against hashlib: chains that end
    on a group's edge and inside one (``blocks % _KB`` 0 and not), in one
    group and in several, so the padding block is folded right after the
    last real block wherever that falls; a batch under a tile rides the
    edge block, and the 1,025th row is a second tile's only lane."""
    from kraken_tpu.ops.sha256 import _digest_bytes

    plen = blocks * 64
    data = np.frombuffer(os.urandom(rows * plen), dtype=np.uint8).reshape(
        rows, plen
    )
    got = _digest_bytes(
        tile_kernels.hash_pieces_device(data, plen, interpret=True)
    )
    for i in range(rows):
        assert bytes(got[i]) == hashlib.sha256(data[i]).digest(), i
