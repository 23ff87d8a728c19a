"""BASELINE row 4 at scale: CDC dedup over a 100+ GB generated corpus.

The round-3 bench measured the cross-layer dedup ratio to 0.81 GB; this
one streams a deterministic synthetic Docker-layer corpus of STREAM_GB
(default 100) through the HOST chunking plane (native C FastCDC,
`kraken_tpu/native/hostcdc.c:kt_cdc_chunk`) with nothing ever written
to disk, and reports the sustained pipeline rate plus the dedup-ratio
curve vs corpus size.

Corpus model (extends bench_dedup.py's): a pool of content files; each
"image build" layer packs FILES_PER_LAYER files as (unique 512 B header +
body), reusing REUSE of the previous build's members, pulling the rest
from the pool, and introducing NEW_PER_LAYER freshly-generated files
(replacing pool slots) -- so the steady-state ratio reflects genuine
content churn, not pool exhaustion. Identity (whole-blob) dedup on this
corpus is 0: every layer differs.

Chunk identity = SHA-256 of chunk bytes (truncated to 128 bits for the
seen-set; collision probability at ~2M chunks is ~1e-26). This bench is
host-plane by design: the device gear-pass rate is measured separately
in bench_dedup.py (marginal method).

    STREAM_GB=100 python bench_cdc_stream.py     # the row-4 run (~6 min)
    STREAM_GB=2 python bench_cdc_stream.py       # quick

Prints ONE JSON line.
"""

import hashlib
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

STREAM_GB = float(os.environ.get("STREAM_GB", 100))
POOL_FILES = int(os.environ.get("CDC_POOL_FILES", 512))
FILE_KB = int(os.environ.get("CDC_FILE_KB", 1024))
FILES_PER_LAYER = int(os.environ.get("CDC_FILES_PER_LAYER", 16))
NEW_PER_LAYER = int(os.environ.get("CDC_NEW_PER_LAYER", 4))
REUSE = float(os.environ.get("CDC_REUSE", 0.8))
CHECKPOINTS_GB = (1, 2, 5, 10, 20, 50, 100, 200, 500, 1000)


def layer_stream(rng: np.random.Generator):
    """Yield (layer_bytes) forever; deterministic for a given seed."""
    pool = [
        rng.integers(0, 256, size=FILE_KB * 1024, dtype=np.uint8).tobytes()
        for _ in range(POOL_FILES)
    ]
    prev: list[int] = []
    while True:
        # Fresh content enters the pool (replacing random slots): the
        # model's genuine-new-bytes rate.
        for _ in range(NEW_PER_LAYER):
            slot = int(rng.integers(0, POOL_FILES))
            pool[slot] = rng.integers(
                0, 256, size=FILE_KB * 1024, dtype=np.uint8
            ).tobytes()
        n_reuse = min(int(FILES_PER_LAYER * REUSE), len(prev))
        reused = (
            list(rng.choice(prev, size=n_reuse, replace=False))
            if prev else []
        )
        fresh = [
            int(i) for i in rng.choice(POOL_FILES, size=FILES_PER_LAYER
                                       - len(reused), replace=False)
        ]
        members = reused + fresh
        rng.shuffle(members)
        parts = []
        for fi in members:
            parts.append(
                rng.integers(0, 256, size=512, dtype=np.uint8).tobytes()
            )
            parts.append(pool[fi])
        yield b"".join(parts)
        prev = members


def main() -> None:
    from kraken_tpu.native import have_native_chunker
    from kraken_tpu.ops.cdc import CDCParams, chunk_host

    params = CDCParams()  # 16/64/256 KiB -- BASELINE config #4
    target = int(STREAM_GB * 1e9)
    rng = np.random.default_rng(7)
    seen: set[bytes] = set()
    total = 0
    dup_bytes = 0
    chunks = 0
    curve: list[dict] = []
    next_cp = iter([int(g * 1e9) for g in CHECKPOINTS_GB])
    cp = next(next_cp)
    t0 = time.perf_counter()
    for layer in layer_stream(rng):
        cuts = chunk_host(layer, params)
        start = 0
        view = memoryview(layer)
        for end in cuts.tolist():
            fp = hashlib.sha256(view[start:end]).digest()[:16]
            if fp in seen:
                dup_bytes += end - start
            else:
                seen.add(fp)
            start = end
        chunks += len(cuts)
        total += len(layer)
        while total >= cp:
            curve.append({
                "gb": round(cp / 1e9),
                "ratio": round(dup_bytes / total, 4),
            })
            try:
                cp = next(next_cp)
            except StopIteration:
                cp = 1 << 62
        if total >= target:
            break
    wall = time.perf_counter() - t0

    print(json.dumps({
        "metric": "cdc_stream_dedup_ratio",
        "value": round(dup_bytes / total, 4),
        "unit": f"fraction at {round(total / 1e9, 1)} GB",
        "vs_baseline": round(dup_bytes / total / 0.30, 3),
        "corpus_gb": round(total / 1e9, 2),
        "pipeline_gbps": round(total / wall / 1e9, 3),
        "chunks": chunks,
        "avg_chunk_kb": round(total / max(1, chunks) / 1024, 1),
        "ratio_curve": curve,
        "native_chunker": have_native_chunker(),
        "unique_chunk_index_mb": round(len(seen) * 85 / 1e6),
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        // 1024,
    }))


if __name__ == "__main__":
    main()
