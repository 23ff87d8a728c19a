"""The layer shape through the origin's upload API, against hashlib.

A layer push (benchmark cell ``origin-tpu-layers.push-layers``) is k full
ingest windows of whole pieces, a last window of 0-15 whole pieces whose row
count the device hasher buckets to a power of two, and a tail shorter than
a piece, sent in a few PATCHes with ``X-Upload-Offset``. Here the same
shape at a small size on the CPU: 64 KiB pieces, 1 MiB windows (16 pieces
a window, as 4 MiB pieces in 64 MiB), the pipelined ingest and
``JaxPieceHasher`` (``hasher: tpu``; the XLA scan stands for the tile
kernels). The served metainfo is compared with hashlib piece by piece, and
each case holds the counters the cell's checks and per-layer metrics read
to the blob: ``hasher_bytes_total`` / ``hasher_pieces_total`` of the device
hasher grow by the blob's bytes and pieces, the host hasher's and
``ingest_fallbacks_total`` stay, and ``ingest_stage_seconds{stage="read"}``
counts at least the blob's windows.

The same six shapes go through ``hasher: tpu-sharded`` (cell
``origin-tpu-sharded.push-layers``) on a mesh of four of the session's
virtual CPU devices, the cell's four chips: a window of whole pieces takes
the ``transfer`` stage and is one ``sha256_sharded`` dispatch of its rows
padded to the mesh's four, a last window with a tail sends its whole pieces
the same way from ``hash_pieces`` and its tail to the single-chip fallback,
and ``hasher_mesh_rows_total{device}`` / ``hasher_mesh_pad_rows_total`` say
which device took how many rows and how many of them were padding.
"""

import asyncio
import hashlib
import json

import numpy as np
import pytest
from aiohttp import ClientSession

from kraken_tpu.assembly import OriginNode
from kraken_tpu.core.digest import Digest
from kraken_tpu.origin.metainfogen import PieceLengthConfig
from kraken_tpu.utils.metrics import REGISTRY

PIECE = 64 * 1024
WINDOW = 16 * PIECE

# (full windows, whole pieces in the last window, tail bytes, PATCHes):
# every value of the three axes once, and the blob that ends on a window's
# edge (its last submit is empty).
SHAPES = [
    (1, 0, 1, 2),
    (1, 1, 63, 3),
    (3, 5, 64, 3),
    (1, 15, PIECE - 1, 2),
    (3, 0, 0, 3),
    (1, 5, 0, 2),
]


MESH = 4  # devices of the sharded cases' mesh: the cell's four chips


def _counters() -> dict:
    c = REGISTRY.counter
    stages = REGISTRY.histogram("ingest_stage_seconds")
    sharded = {"purpose": "piece", "kernel": "sha256_sharded"}
    out = {
        "cpu_bytes": c("hasher_bytes_total").value(hasher="cpu"),
        "cpu_pieces": c("hasher_pieces_total").value(hasher="cpu"),
        "fallbacks": c("ingest_fallbacks_total").total(),
        "reads": stages.count(stage="read"),
        "hashes": stages.count(stage="hash"),
        "transfers": stages.count(stage="transfer"),
        "sharded_sections": c("hasher_device_sections_total").value(**sharded),
        "sharded_rows": c("hasher_device_rows_total").value(**sharded),
        "mesh_rows": c("hasher_mesh_rows_total").total(),
        "mesh_pad_rows": c("hasher_mesh_pad_rows_total").total(),
    }
    for name in ("tpu", "tpu-sharded"):
        out[name + "_bytes"] = c("hasher_bytes_total").value(hasher=name)
        out[name + "_pieces"] = c("hasher_pieces_total").value(hasher=name)
    for d in range(MESH):
        out[f"mesh_rows_{d}"] = c("hasher_mesh_rows_total").value(device=str(d))
    return out


async def _push(addr: str, blob: bytes, patches: int) -> dict:
    """POST, ``patches`` PATCHes cut at odd places, commit, GET metainfo."""
    d = Digest.from_bytes(blob)
    base = f"http://{addr}/namespace/ns/blobs/{d}"
    # Cuts that fall inside a piece and inside a window, never on an edge.
    cuts = sorted({0, len(blob)} | {
        min(len(blob), len(blob) * i // patches + 17) for i in range(1, patches)
    })
    async with ClientSession() as http:
        async with http.post(f"{base}/uploads") as r:
            assert r.status == 200
            uid = await r.text()
        for a, b in zip(cuts, cuts[1:]):
            async with http.patch(
                f"{base}/uploads/{uid}", data=blob[a:b],
                headers={"X-Upload-Offset": str(a)},
            ) as r:
                assert r.status == 204
        async with http.put(f"{base}/uploads/{uid}/commit") as r:
            assert r.status == 201, await r.text()
        async with http.get(f"{base}/metainfo") as r:
            assert r.status == 200
            return json.loads(await r.read())


@pytest.fixture
def four_device_mesh(monkeypatch):
    """``get_hasher("tpu-sharded")`` builds its mesh over every device of
    the platform (the session has eight); the node is handed the cell's
    four through the registry's own cache of instances."""
    from kraken_tpu.core import hasher as registry
    from kraken_tpu.parallel import ShardedPieceHasher, piece_mesh

    monkeypatch.setitem(
        registry._INSTANCES, "tpu-sharded",
        ShardedPieceHasher(mesh=piece_mesh(MESH)),
    )


@pytest.mark.parametrize("hasher", ["tpu", "tpu-sharded"])
@pytest.mark.parametrize("windows,whole,tail,patches", SHAPES)
def test_layer_shape_matches_hashlib_and_moves_the_cells_counters(
    tmp_path, four_device_mesh, windows, whole, tail, patches, hasher,
):
    size = windows * WINDOW + whole * PIECE + tail
    rng = np.random.default_rng([33, windows, whole, tail])
    blob = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    n_pieces = -(-size // PIECE)

    async def main():
        node = OriginNode(
            store_root=str(tmp_path / "o"), hasher=hasher, dedup=False,
            piece_lengths=PieceLengthConfig(table=((0, PIECE),)),
            ingest={"window_bytes": WINDOW, "windows_in_flight": 2},
        )
        await node.start()
        try:
            return await _push(node.addr, blob, patches)
        finally:
            await node.stop()

    before = _counters()
    doc = asyncio.run(main())
    after = _counters()
    grew = {k: after[k] - before[k] for k in before}

    info = doc["info"]
    assert doc["digest"] == "sha256:" + hashlib.sha256(blob).hexdigest()
    assert (info["length"], info["piece_length"]) == (size, PIECE)
    served = bytes.fromhex(info["piece_hashes"])
    assert len(served) == 32 * n_pieces
    for i in range(n_pieces):
        want = hashlib.sha256(blob[i * PIECE:(i + 1) * PIECE]).digest()
        assert served[32 * i:32 * i + 32] == want, f"piece {i} of {n_pieces}"

    # What the cell's counter checks read: the device hasher covered the
    # payload, exactly once, and nothing went through the host.
    other = "tpu-sharded" if hasher == "tpu" else "tpu"
    assert grew[hasher + "_bytes"] == size
    assert grew[hasher + "_pieces"] == n_pieces
    assert grew[other + "_bytes"] == 0 and grew[other + "_pieces"] == 0
    assert grew["cpu_bytes"] == 0 and grew["cpu_pieces"] == 0
    assert grew["fallbacks"] == 0
    # What ingest_read_s and ingest_hash_s divide by: a read a window
    # submitted (an empty last one too), a hash a window that held bytes.
    held = windows + (1 if whole or tail else 0)
    assert grew["reads"] >= held
    assert grew["hashes"] == held

    if hasher == "tpu":
        for name in ("transfers", "sharded_sections", "mesh_rows", "mesh_pad_rows"):
            assert grew[name] == 0, name
        return
    # What ingest_transfer_s divides by: the windows that held whole pieces
    # only (a last window with a tail goes through hash_pieces whole).
    assert grew["transfers"] == windows + (1 if whole and not tail else 0)
    # What mesh_rows_mean.push reads: a dispatch a window that held a whole
    # piece, its rows padded to the mesh's device quantum.
    pad = (-whole) % MESH
    rows = windows * (WINDOW // PIECE) + whole + pad
    assert grew["sharded_sections"] == windows + (1 if whole else 0)
    assert grew["sharded_rows"] == rows
    # Every device took its equal share, and the padding is counted apart.
    assert grew["mesh_rows"] == rows and grew["mesh_pad_rows"] == pad
    for d in range(MESH):
        assert grew[f"mesh_rows_{d}"] == rows // MESH, f"device {d}"
