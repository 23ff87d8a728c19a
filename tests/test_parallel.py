"""Multi-device sharding tests for the hash plane.

conftest.py forces an 8-way virtual CPU mesh for the whole session, so
shard_map collectives run for real here (the permanent in-suite multi-chip
signal; the driver's dryrun_multichip covers the same path out-of-suite).
"""

import hashlib
import os

import numpy as np
import pytest

from kraken_tpu.core.hasher import get_hasher
from kraken_tpu.ops.sha256 import _digest_bytes
from kraken_tpu.parallel import (
    ShardedPieceHasher,
    piece_mesh,
    sharded_hash_pieces,
)


def _want(data: np.ndarray) -> list[bytes]:
    return [hashlib.sha256(row.tobytes()).digest() for row in data]


def test_piece_mesh_has_eight_devices():
    mesh = piece_mesh(8)
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("pieces",)
    assert mesh.devices.flat[0].platform == "cpu"


def test_piece_mesh_never_moves_to_another_platform(monkeypatch):
    """Asking for more devices than the default platform has raises. The
    mesh used to move to virtual CPU devices in that case, and a
    `tpu-sharded` hasher on them hashed correctly and said nothing."""
    import jax

    class OneChip:
        platform = "tpu"
        id = 0

    asked = []

    def devices(platform=None):
        asked.append(platform)
        return [OneChip()]

    monkeypatch.setattr(jax, "devices", devices)
    with pytest.raises(ValueError, match="need 4 tpu devices, have 1"):
        piece_mesh(4)
    assert asked == [None]


def test_sharded_hasher_says_where_its_rows_went(caplog):
    """`device_info` is what the READY line prints; the first dispatch
    logs the rows each device holds (chip_smoke.py --four-chips reads
    both to prove four chips took work)."""
    import logging

    hasher = ShardedPieceHasher(mesh=piece_mesh(8))
    assert hasher.device_info() == {
        "platform": "cpu", "device_kind": "cpu", "count": 8,
    }
    blob = os.urandom(16 * 256)
    with caplog.at_level(logging.INFO, logger="kraken.hashplane"):
        hasher.hash_pieces(blob, 256)
        hasher.hash_pieces(blob, 256)
    first = [r for r in caplog.records if r.name == "kraken.hashplane"]
    assert len(first) == 1  # once per hasher, not per dispatch
    assert first[0].rows_per_device == {str(i): 2 for i in range(8)}


# The Pallas variant is opt-in: XLA:CPU needs >5 min to compile the
# kernel's unrolled body in any CPU mode (see dryrun_multichip docstring);
# the kernel's correctness home is the real chip (entry() + chip_smoke.py).
_PALLAS = (
    [False, True] if os.environ.get("RUN_PALLAS_INTERPRET") else [False]
)


@pytest.mark.parametrize("use_pallas", _PALLAS)
def test_sharded_hash_matches_hashlib(use_pallas):
    mesh = piece_mesh(8)
    piece_len = 256
    n = 8 * 3 + 5  # ragged vs the device quantum: exercises row padding
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(n, piece_len), dtype=np.uint8)
    out = sharded_hash_pieces(
        mesh, data, piece_len, use_pallas=use_pallas, replicate=True
    )
    assert out.shape == (n, 8)
    got = _digest_bytes(out)
    want = _want(data)
    for i in range(n):
        assert got[i].tobytes() == want[i], f"piece {i} (pallas={use_pallas})"


# The whole pieces a layer's last window holds in the deck of the cells
# `origin-tpu*-*.push-layers` (benchmark/traffic/layers-100m-1g.json), and
# the full window's 16, on the four-chip cell's mesh: each is padded to the
# mesh's quantum (4, 12 or 16 rows), hashed a quarter a device, and the
# padding's digests are cut off again.
@pytest.mark.parametrize("rows", [1, 2, 4, 9, 10, 11, 12, 13, 16])
def test_last_window_row_counts_on_four_devices_match_hashlib(rows):
    piece_len = 4096
    hasher = ShardedPieceHasher(mesh=piece_mesh(4))
    blob = np.random.default_rng([36, rows]).integers(
        0, 256, size=rows * piece_len, dtype=np.uint8).tobytes()
    want = [hashlib.sha256(blob[i * piece_len:(i + 1) * piece_len]).digest()
            for i in range(rows)]
    staged = hasher.hash_staged_window(hasher.stage_window(
        np.frombuffer(blob, dtype=np.uint8).reshape(rows, piece_len), piece_len))
    assert [bytes(d) for d in staged] == want
    # hash_pieces sends the same rows the same way, then a tail of its own.
    tailed = hasher.hash_pieces(blob + b"tail", piece_len)
    assert [bytes(d) for d in tailed] == want + [hashlib.sha256(b"tail").digest()]


def test_sharded_output_replicated():
    mesh = piece_mesh(8)
    data = np.zeros((16, 128), dtype=np.uint8)
    out = sharded_hash_pieces(mesh, data, 128, replicate=True)
    # Replicated: every device holds the full digest matrix.
    assert out.sharding.is_fully_replicated


def test_sharded_hasher_registry_roundtrip():
    hasher = get_hasher("tpu-sharded")
    assert isinstance(hasher, ShardedPieceHasher)
    rng = np.random.default_rng(3)
    # 10 full 256-byte pieces + a 100-byte ragged tail.
    blob = rng.integers(0, 256, size=10 * 256 + 100, dtype=np.uint8).tobytes()
    got = hasher.hash_pieces(blob, 256)
    assert got.shape == (11, 32)
    for i in range(11):
        want = hashlib.sha256(blob[i * 256 : (i + 1) * 256]).digest()
        assert got[i].tobytes() == want, f"piece {i}"


def test_graft_dryrun_is_hermetic():
    """The dryrun must pass with a HOSTILE parent environment.

    Round-2 regression: the driver gate failed because the dryrun depended
    on the driver's XLA_FLAGS for device count and let an eager gather
    index land on the default (real, version-skewed) TPU device. The
    subprocess re-exec must scrub both: bogus JAX_PLATFORMS, no XLA_FLAGS.
    Inside the dryrun, transfer_guard_host_to_device("disallow") turns any
    stray implicit default-device placement into a hard failure.
    """
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"  # bogus here: no TPU in the test env
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import __graft_entry__; __graft_entry__.dryrun_multichip(8)",
        ],
        env=env,
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"


def test_tpu_sharded_hasher_resolvable_by_name(tmp_path):
    """`hasher: tpu-sharded` in component YAML resolves through the
    registry (deferred hashplane import) and hashes correctly -- the
    production multi-chip path, end to end through a node."""

    from kraken_tpu.origin.metainfogen import Generator
    from kraken_tpu.store import CAStore
    from kraken_tpu.core.digest import Digest

    h = get_hasher("tpu-sharded")
    data = np.random.default_rng(3).integers(
        0, 256, size=300_000, dtype=np.uint8
    ).tobytes()
    got = h.hash_pieces(data, 65536)
    want = [
        hashlib.sha256(data[o : o + 65536]).digest()
        for o in range(0, len(data), 65536)
    ]
    assert [bytes(r) for r in got] == want

    # And through the origin's metainfo generator (the real hot loop).
    store = CAStore(str(tmp_path))
    d = Digest.from_bytes(data)
    uid = store.create_upload()
    store.write_upload_chunk(uid, 0, data)
    store.commit_upload(uid, d)
    gen = Generator(store, hasher=h)
    mi = gen.generate_sync(d)
    assert mi.length == len(data)
    # The generator's chunked read path must produce byte-exact digests
    # (it chooses its own piece length from the blob-size table).
    pl = mi.piece_length
    want_mi = [
        hashlib.sha256(data[o : o + pl]).digest()
        for o in range(0, len(data), pl)
    ]
    assert [mi.piece_hash(i) for i in range(mi.num_pieces)] == want_mi
