"""Dedup-plane benchmark: cross-layer dedup ratio on a synthetic corpus.

BASELINE.json config #4: FastCDC over a Docker-layer-like corpus, 64 KiB
average chunks; north-star target >= 30% cross-layer dedup. Prints ONE
JSON line:

    {"metric": "cdc_cross_layer_dedup_ratio", "value": ..., "unit":
     "fraction", "vs_baseline": value/0.30, "chunk_gbps": ...,
     "identity_dedup_ratio": ...}

The synthetic corpus models what defeats fixed-size dedup in registries:
layers share file *content* but at different byte offsets (tar headers,
file ordering, prepended metadata differ per image build). Each layer is
a tar-like stream of (512 B unique header + shared-or-unique file body);
consecutive "image builds" reuse most files, reorder some, and patch a
few. ``identity_dedup_ratio`` is what whole-blob dedup (the reference's
only mechanism: content-addressed identical blobs) achieves on the same
corpus -- the delta is the capability this plane adds.

Round 9 adds the cash-in row: ``delta_bytes_moved_ratio`` -- bytes a
REAL agent pull actually fetches (swarm piece ingress + origin range
GETs, registry-counted) divided by blob size, on consecutive
build-over-build pulls through a live tracker+origin+agent herd with
the chunk-level delta-transfer plane ON, against the delta-off control
(median +/- IQR over ``DEDUP_DELTA_LAYERS-1`` pulls). The sub-corpus is
the same generator at image-shaped file sizes (``DEDUP_DELTA_FILE_KB``,
default 1 MiB -- see the DELTA_* knob comments for why the headline
corpus's 192 KB files are below the production CDC's resolution). The
detected dedup ratio is the *ceiling*; this row is what the wire now
*moves*. tests/test_delta.py::test_delta_pull_band pins the same
measurement as a tier-1 CI band (delta-on <= 0.6x of control).

Run on TPU (default platform) or CPU (JAX_PLATFORMS=cpu). The chunking
rate reported is the end-to-end two-phase chunker (device gear-hash pass +
host cut selection).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_FILES = int(os.environ.get("DEDUP_FILES", 96))
FILE_KB = int(os.environ.get("DEDUP_FILE_KB", 192))
N_LAYERS = int(os.environ.get("DEDUP_LAYERS", 24))
FILES_PER_LAYER = int(os.environ.get("DEDUP_FILES_PER_LAYER", 24))
REUSE = float(os.environ.get("DEDUP_REUSE", 0.8))  # share of reused files
# Delta e2e sub-corpus (same generator, image-shaped file sizes): the
# planner's win tracks chunks-per-file, and the headline corpus's 192 KB
# files sit at the production 64 KiB-avg CDC resolution floor (~3
# chunks/file -> ~0.2 duplicate fraction vs the previous build even
# though file REUSE is 0.8). Real build-over-build layers carry multi-MB
# files (shared libs, venvs); 1 MiB files give ~16 chunks/file and a
# 0.6-0.8 vs-prev duplicate fraction -- the regime delta transfer is for.
DELTA_LAYERS = int(os.environ.get("DEDUP_DELTA_LAYERS", 8))  # e2e pulls
DELTA_FILE_KB = int(os.environ.get("DEDUP_DELTA_FILE_KB", 1024))
DELTA_FILES_PER_LAYER = int(os.environ.get("DEDUP_DELTA_FILES_PER_LAYER", 8))


def make_corpus(
    rng: np.random.Generator,
    n_files: int | None = None,
    file_kb: int | None = None,
    n_layers: int | None = None,
    files_per_layer: int | None = None,
) -> list[bytes]:
    n_files = N_FILES if n_files is None else n_files
    file_kb = FILE_KB if file_kb is None else file_kb
    n_layers = N_LAYERS if n_layers is None else n_layers
    files_per_layer = (
        FILES_PER_LAYER if files_per_layer is None else files_per_layer
    )
    files = [
        rng.integers(0, 256, size=file_kb * 1024, dtype=np.uint8).tobytes()
        for _ in range(n_files)
    ]
    layers = []
    prev: list[int] = []
    for li in range(n_layers):
        n_reuse = int(files_per_layer * REUSE) if prev else 0
        reused = list(rng.choice(prev, size=min(n_reuse, len(prev)),
                                 replace=False)) if prev else []
        fresh = list(rng.choice(
            [i for i in range(n_files) if i not in reused],
            size=files_per_layer - len(reused), replace=False))
        members = reused + fresh
        rng.shuffle(members)
        parts = []
        for fi in members:
            header = rng.integers(0, 256, size=512, dtype=np.uint8).tobytes()
            parts.append(header)
            parts.append(files[fi])
        layers.append(b"".join(parts))
        prev = members
    return layers


async def _delta_herd(layers: list[bytes], root: str, on: bool) -> dict:
    """Pull ``layers`` in build order through a live tracker+origin+agent
    herd; returns ``{"ratios": [...], "stored_bytes": n}`` where ratios
    are bytes-moved/blob-size for every build-over-build pull (the first
    pull -- cold cache, necessarily ~1.0 -- is excluded) and
    stored_bytes is the agent store's end-of-run disk usage. "Moved" is
    what the agent actually fetched: swarm piece ingress
    (``p2p_piece_bytes_down_total``) plus delta range GETs
    (``delta_bytes_fetched_total``), read as registry deltas around each
    pull. With ``on`` True the agent ALSO runs the chunk store tier
    (store/chunkstore.py), so stored_bytes measures the at-rest cash-in
    next to the wire one; ``on`` False runs the shipped defaults (both
    off): the control both ratio rows are quoted against."""
    from urllib.parse import quote

    from kraken_tpu.assembly import AgentNode, OriginNode, TrackerNode
    from kraken_tpu.core.digest import Digest
    from kraken_tpu.origin.client import BlobClient, ClusterClient
    from kraken_tpu.origin.metainfogen import PieceLengthConfig
    from kraken_tpu.placement import HostList, Ring
    from kraken_tpu.utils.httputil import HTTPClient
    from kraken_tpu.utils.metrics import REGISTRY

    ns = "library/bench-delta"
    tracker = TrackerNode(announce_interval_seconds=0.1)
    await tracker.start()
    origin = OriginNode(
        store_root=os.path.join(root, "origin"),
        tracker_addr=tracker.addr,
        # 256 KiB pieces: a ~5 MB layer carries ~19 pieces, so planning
        # exercises both fully-covered pieces and range-filled holes.
        piece_lengths=PieceLengthConfig(table=((0, 262144),)),
        delta={"enabled": True} if on else None,
    )
    await origin.start()
    ring = Ring(HostList(static=[origin.addr]), max_replica=2)
    cluster = ClusterClient(ring)
    tracker.server.origin_cluster = cluster
    agent = AgentNode(
        store_root=os.path.join(root, "agent"),
        tracker_addr=tracker.addr,
        delta={"enabled": True, "min_blob_bytes": 1} if on else None,
        chunkstore=(
            {"enabled": True, "min_blob_bytes": 1} if on else None
        ),
    )
    await agent.start()
    http = HTTPClient()
    oc = BlobClient(origin.addr)
    down = REGISTRY.counter("p2p_piece_bytes_down_total")
    fetched = REGISTRY.counter("delta_bytes_fetched_total")
    ratios: list[float] = []
    try:
        for i, blob in enumerate(layers):
            d = Digest.from_bytes(blob)
            await oc.upload(ns, d, blob)
            d0, f0 = down.value(), fetched.value()
            got = await http.get(
                f"http://{agent.addr}/namespace/"
                f"{quote(ns, safe='')}/blobs/{d.hex}"
            )
            assert got == blob, "pulled blob must be bit-identical"
            moved = (down.value() - d0) + (fetched.value() - f0)
            if i > 0:
                ratios.append(moved / len(blob))
            if on:
                # Conversion runs as a background task after each pull;
                # wait it out so the NEXT pull's delta plan copies from
                # a chunk-backed base and the end-of-run disk usage
                # reflects the tier, not an in-flight flat file.
                deadline = asyncio.get_running_loop().time() + 30.0
                while (
                    not agent.store.is_chunked(d)
                    and asyncio.get_running_loop().time() < deadline
                ):
                    await asyncio.sleep(0.05)
        stored = agent.store.disk_usage_bytes()
    finally:
        await http.close()
        await oc.close()
        await agent.stop()
        await origin.stop()
        await cluster.close()
        await tracker.stop()
    return {"ratios": ratios, "stored_bytes": stored}


def delta_moved_rows(rng: np.random.Generator) -> dict:
    """The delta-transfer cash-in rows: median +/- IQR of the per-pull
    bytes-moved ratio, delta-on vs the delta-off control, over
    ``DELTA_LAYERS - 1`` build-over-build pulls of an image-shaped
    sub-corpus (``DELTA_FILE_KB`` files; see the module docstring)."""
    import asyncio
    import tempfile

    sub = make_corpus(
        rng, n_files=4 * DELTA_FILES_PER_LAYER, file_kb=DELTA_FILE_KB,
        n_layers=DELTA_LAYERS, files_per_layer=DELTA_FILES_PER_LAYER,
    )
    with tempfile.TemporaryDirectory() as tmp:
        res_on = asyncio.run(_delta_herd(sub, os.path.join(tmp, "on"), True))
        res_off = asyncio.run(
            _delta_herd(sub, os.path.join(tmp, "off"), False)
        )
    on, off = res_on["ratios"], res_off["ratios"]

    def q(vals, p):
        return round(float(np.percentile(vals, p)), 4)

    return {
        "delta_bytes_moved_ratio": q(on, 50),
        "delta_bytes_moved_ratio_iqr": [q(on, 25), q(on, 75)],
        "delta_off_bytes_moved_ratio": q(off, 50),
        "delta_off_bytes_moved_ratio_iqr": [q(off, 25), q(off, 75)],
        "delta_vs_off": round(q(on, 50) / max(q(off, 50), 1e-9), 4),
        "delta_pulls": len(on),
        # The at-rest cash-in (store/chunkstore.py): end-of-run agent
        # disk usage, chunk tier vs the flat-blob control, over the
        # same build-over-build pulls. tests/test_chunkstore.py pins
        # the same measurement as a tier-1 band (<= 0.7x of control).
        "delta_bytes_stored_ratio": round(
            res_on["stored_bytes"] / max(res_off["stored_bytes"], 1), 4
        ),
        "delta_stored_bytes": res_on["stored_bytes"],
        "delta_off_stored_bytes": res_off["stored_bytes"],
    }


def main() -> None:
    import hashlib

    from kraken_tpu.ops.cdc import CDCParams, chunk_spans

    rng = np.random.default_rng(7)
    layers = make_corpus(rng)
    total = sum(len(b) for b in layers)

    # Whole-blob (reference-style) dedup baseline.
    seen_blobs: set[bytes] = set()
    identity_dup = 0
    for b in layers:
        h = hashlib.sha256(b).digest()
        if h in seen_blobs:
            identity_dup += len(b)
        else:
            seen_blobs.add(h)

    params = CDCParams()  # 16/64/256 KiB -- BASELINE config #4
    seen: set[bytes] = set()
    dup_bytes = 0
    t0 = time.perf_counter()
    for blob in layers:
        for s, e in chunk_spans(blob, params):
            fp = hashlib.sha256(blob[s:e]).digest()
            if fp in seen:
                dup_bytes += e - s
            else:
                seen.add(fp)
    dt = time.perf_counter() - t0

    ratio = dup_bytes / total

    # Delta-transfer cash-in: what a real pull MOVES, on vs off.
    delta_rows = delta_moved_rows(rng)

    # Device gear-pass rate with the data resident (marginal method);
    # the chunk wall clock above includes the host->device copy.
    import jax
    import jax.numpy as jnp

    from kraken_tpu.ops.cdc_pallas import _BUF, _ROWS, _T_DISPATCH, _gear_pallas

    # The production large-blob path: the Pallas VMEM-doubling kernel,
    # fed the [T, rows, 128] segment layout with data resident.
    n = _T_DISPATCH * (_BUF - 1024)
    dev = jax.random.bits(
        jax.random.PRNGKey(0), (_T_DISPATCH, _ROWS, 128), dtype=jnp.uint8
    )
    dev.block_until_ready()
    ms, ml = params.mask_strict, params.mask_loose

    def dispatch():
        return _gear_pallas(dev, ms, ml)[0]

    np.asarray(dispatch()[0, 0])
    def timed(k):
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = dispatch()
        np.asarray(out[0, 0])
        return time.perf_counter() - t0
    # Latency jitter between the fences swamps small marginal windows;
    # queue 40 extra 64 MiB dispatches (2.5 GB) per trial.
    rates = []
    for _ in range(5):
        t_s, t_l = timed(2), timed(42)
        rates.append(40 * n / max(t_l - t_s, 1e-9) / 1e9)
    gear_gbps = sorted(rates)[len(rates) // 2]

    print(
        json.dumps(
            {
                "metric": "cdc_cross_layer_dedup_ratio",
                "value": round(ratio, 4),
                "unit": "fraction",
                "vs_baseline": round(ratio / 0.30, 3),
                "gear_pass_gbps": round(gear_gbps, 2),
                "chunk_wallclock_gbps": round(total / dt / 1e9, 3),
                "identity_dedup_ratio": round(identity_dup / total, 4),
                **delta_rows,
                "corpus_bytes": total,
                "layers": N_LAYERS,
            }
        )
    )


if __name__ == "__main__":
    main()
