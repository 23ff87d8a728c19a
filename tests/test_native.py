"""Golden tests for the native host packer (C, AVX-512 w/ scalar fallback).

The packed layout feeds the production Pallas path; a silent layout bug
would produce wrong digests at 80+ GB/s, so the C output is checked
element-exactly against an independent NumPy construction.
"""

import numpy as np
import pytest

from kraken_tpu import native


def _reference(data: np.ndarray, nb_out: int) -> np.ndarray:
    m, piece_len = data.shape
    t, nbd = m // 1024, piece_len // 64
    w = data.reshape(t, 1024, nbd, 16, 4)
    be = (
        (w[..., 0].astype(np.uint32) << 24)
        | (w[..., 1].astype(np.uint32) << 16)
        | (w[..., 2].astype(np.uint32) << 8)
        | w[..., 3].astype(np.uint32)
    )
    out = np.zeros((t, nb_out, 16, 1024), dtype=np.uint32)
    out[:, :nbd] = be.transpose(0, 2, 3, 1)
    return out


@pytest.mark.parametrize("piece_len,tiles", [(64, 1), (576, 1), (4096, 2)])
def test_pack_tiles_matches_reference(piece_len, tiles):
    rng = np.random.default_rng(piece_len)
    data = rng.integers(0, 256, size=(1024 * tiles, piece_len), dtype=np.uint8)
    nb_out = ((piece_len // 64 + 7) // 8) * 8  # packed_nb for _KB=8
    got = native.pack_tiles(data, nb_out)
    assert np.array_equal(got, _reference(data, nb_out))


def test_pack_tiles_validates_shape():
    with pytest.raises(ValueError):
        native.pack_tiles(np.zeros((100, 64), dtype=np.uint8), 1)
    with pytest.raises(ValueError):
        native.pack_tiles(np.zeros((1024, 63), dtype=np.uint8), 1)


@pytest.mark.parametrize("threads", [1, 3, 8, 64])
def test_pack_tiles_threaded_matches_single(threads):
    """The pthread fan-out over 16-piece groups must be bit-identical to
    the single-threaded pack for every thread count (including more
    threads than groups, which clamps)."""
    if not native.have_native_packer():
        pytest.skip("no C toolchain")
    rng = np.random.default_rng(threads)
    data = rng.integers(0, 256, size=(2048, 448), dtype=np.uint8)
    nb_out = 8
    base = native.pack_tiles(data, nb_out, threads=1)
    got = native.pack_tiles(data, nb_out, threads=threads)
    assert np.array_equal(got, base)
    assert np.array_equal(got, _reference(data, nb_out))


def test_scalar_and_simd_paths_agree():
    """The runtime-dispatched C path must agree with the NumPy fallback
    (covers both when the build has AVX-512 and when it doesn't)."""
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(1024, 128), dtype=np.uint8)
    c_out = native.pack_tiles(data, 2)
    lib = native._LIB
    try:
        native._LIB = None
        py_out = native.pack_tiles(data, 2)
    finally:
        native._LIB = lib
    assert np.array_equal(c_out, py_out)


def test_native_cdc_chunker_matches_reference():
    """The C chunker and the NumPy fallback both produce chunk_reference's
    exact cuts -- boundaries are a persistent on-disk contract."""

    import kraken_tpu.native as nat
    from kraken_tpu.ops.cdc import CDCParams, chunk_host, chunk_reference

    p = CDCParams(min_size=64, avg_size=256, max_size=1024)
    rng = np.random.default_rng(3)
    for n in (0, 1, 63, 64, 65, 255, 4096, 20000):
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        ref = chunk_reference(data, p) if n else []
        assert chunk_host(data, p).tolist() == ref, n
        lib, nat._LIB = nat._LIB, None  # force the NumPy fallback
        try:
            assert chunk_host(data, p).tolist() == ref, ("numpy", n)
        finally:
            nat._LIB = lib
    # Low-entropy data (max_size forcing) and default params.
    data = b"\x00" * 300_000
    pd = CDCParams()
    ref = chunk_reference(data, pd)
    assert chunk_host(data, pd).tolist() == ref
    assert ref[0] == pd.max_size  # constant data never hits a mask


def test_pack_tiles_range_matches_reference():
    """Cooperative range packing (the GIL-free HashPool entry): disjoint
    group stripes written by separate calls must reassemble to exactly
    the single-call layout, including out-of-range clamping."""
    if not native.have_native_packer():
        pytest.skip("no native packer on this rig")
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(2048, 576), dtype=np.uint8)
    nb_out = 16
    out = np.zeros((2, nb_out, 16, 1024), dtype=np.uint32)
    n_groups = 2048 // 16
    # Three unequal stripes + a deliberately overshooting upper bound.
    native.pack_tiles_range(data, nb_out, out, 0, 17)
    native.pack_tiles_range(data, nb_out, out, 17, 100)
    native.pack_tiles_range(data, nb_out, out, 100, n_groups + 50)
    assert np.array_equal(out, _reference(data, nb_out))


def test_pack_tiles_pooled_matches_reference():
    """pack_tiles_pooled through a real HashPool must be bit-exact (and
    fall back cleanly when the pool can't help)."""
    from kraken_tpu.core.hasher import HashPool

    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, size=(2048, 576), dtype=np.uint8)
    want = _reference(data, 16)
    pool = HashPool(2, name="test-pack")
    assert np.array_equal(native.pack_tiles_pooled(data, 16, pool), want)
    # pool=None falls back to the single-call path.
    assert np.array_equal(native.pack_tiles_pooled(data, 16, None), want)


def test_pack_out_buffer_validation():
    """Caller-supplied `out` (a bufpool staging lease in production) is
    validated for dtype, shape, contiguity, and writability before any
    raw pointer reaches the C packer."""
    data = np.zeros((1024, 64), dtype=np.uint8)
    with pytest.raises(ValueError):  # wrong dtype
        native.pack_tiles(data, 8, out=np.zeros((1, 8, 16, 1024), np.uint64))
    with pytest.raises(ValueError):  # wrong shape
        native.pack_tiles(data, 8, out=np.zeros((1, 8, 16, 512), np.uint32))
    big = np.zeros((1, 8, 16, 2048), dtype=np.uint32)
    with pytest.raises(ValueError):  # non-contiguous view
        native.pack_tiles(data, 8, out=big[:, :, :, ::2])
    ro = np.zeros((1, 8, 16, 1024), dtype=np.uint32)
    ro.setflags(write=False)
    with pytest.raises(ValueError):  # read-only
        native.pack_tiles(data, 8, out=ro)


def test_shared_object_is_keyed_by_source_and_host(monkeypatch, tmp_path):
    """The object is built from hostpack.c on the host that loads it: its
    name carries a hash of the source and of the host's identity, so one
    that arrived with a copied tree (the old fixed name `_hostpack.so`,
    built elsewhere with -march=native) is never picked up."""
    import os

    if not native.have_native_packer():
        pytest.skip("no C toolchain on this rig")
    here = native._build()
    assert os.path.basename(here) != "_hostpack.so"
    assert native._build() == here  # stable on one host
    monkeypatch.setattr(native, "_host_identity", lambda: b"another host")
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    elsewhere = native._build()
    assert elsewhere is not None and os.path.exists(elsewhere)
    assert os.path.basename(elsewhere) != os.path.basename(here)


def test_pooled_pack_scales_with_workers():
    """On a multi-core rig, 2 pack workers must beat 1 by a real margin
    (the pack loop is GIL-free and group-parallel). Interleaved pairwise
    timing so machine noise hits both configs alike."""
    import os
    import time

    if (os.cpu_count() or 1) < 2:
        pytest.skip("scaling pin needs >= 2 cores")
    if not native.have_native_packer():
        pytest.skip("no native packer on this rig")
    from kraken_tpu.core.hasher import HashPool

    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, size=(8192, 4096), dtype=np.uint8)
    out = np.zeros((8, 64, 16, 1024), dtype=np.uint32)
    pool1 = HashPool(1, name="scale1")
    pool2 = HashPool(2, name="scale2")

    def run(pool) -> float:
        t0 = time.perf_counter()
        native.pack_tiles_pooled(data, 64, pool, out=out)
        return time.perf_counter() - t0

    for pool in (pool2, pool1):  # warm caches + pool threads
        run(pool)
    ratios = []
    for _ in range(5):
        t1, t2 = run(pool1), run(pool2)
        ratios.append(t1 / t2)
    ratios.sort()
    assert ratios[len(ratios) // 2] >= 1.3, ratios
