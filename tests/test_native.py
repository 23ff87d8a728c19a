"""Golden tests for the native host chunker (C, ctypes-bound).

Chunk boundaries are a persistent on-disk contract, so the C chunker and
its NumPy fallback are checked cut for cut against ``chunk_reference``.
"""

import numpy as np
import pytest

from kraken_tpu import native
from kraken_tpu.ops.cdc import CDCParams, chunk_host, chunk_reference

_SMALL = CDCParams(min_size=64, avg_size=256, max_size=1024)


def _random(n: int) -> bytes:
    return np.random.default_rng(3 + n).integers(
        0, 256, size=n, dtype=np.uint8
    ).tobytes()


@pytest.mark.parametrize(
    "data,params",
    [
        pytest.param(_random(n), _SMALL, id=str(n))
        for n in (0, 1, 63, 64, 65, 255, 4096, 20000)
    ]
    # Low-entropy data (max_size forcing) and default params.
    + [pytest.param(b"\x00" * 300_000, CDCParams(), id="zeros-default")],
)
def test_native_cdc_chunker_matches_reference(data, params):
    """The C chunker and the NumPy fallback both produce chunk_reference's
    exact cuts -- boundaries are a persistent on-disk contract."""
    ref = chunk_reference(data, params) if data else []
    assert chunk_host(data, params).tolist() == ref
    lib, native._LIB = native._LIB, None  # force the NumPy fallback
    try:
        assert chunk_host(data, params).tolist() == ref, "numpy"
    finally:
        native._LIB = lib
    if data and not any(data):
        assert ref[0] == params.max_size  # constant data never hits a mask


def test_shared_object_is_keyed_by_source_and_host(monkeypatch, tmp_path):
    """The object is built from hostcdc.c on the host that loads it: its
    name carries a hash of the source and of the host's identity, so one
    that arrived with a copied tree (a fixed name like `_hostcdc.so`,
    built elsewhere with -march=native) is never picked up."""
    import os

    if not native.have_native_chunker():
        pytest.skip("no C toolchain on this rig")
    here = native._build()
    assert os.path.basename(here).startswith("_hostcdc-")
    assert native._build() == here  # stable on one host
    monkeypatch.setattr(native, "_host_identity", lambda: b"another host")
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    elsewhere = native._build()
    assert elsewhere is not None and os.path.exists(elsewhere)
    assert os.path.basename(elsewhere) != os.path.basename(here)
