"""North-star benchmark: batched SHA-256 piece hashing throughput.

Measures the TPU metainfo-gen hot loop (BASELINE.json config #3: batched
SHA-256 over uniform pieces; target >= 20 GB/s/chip on v5e) against the CPU
hashlib baseline (config #1), printing ONE JSON line:

    {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...,
     "packed_kernel_gbps": ..., "host_pack_gbps_core": ...}

``value`` is the NATURAL-layout device path (what ``hash_pieces`` delivers
from raw piece bytes with no host-side packing) -- the honest end-to-end
chip number. ``packed_kernel_gbps`` is the same kernel fed the word-major
layout the native host packer produces at staging time (the production
origin configuration); ``host_pack_gbps_core`` is that packer's measured
single-core rate here. PERF.md holds the full measured analysis.

``vs_baseline`` is the headline/CPU speedup -- the reference hashes pieces
sequentially on the CPU (uber/kraken lib/metainfogen [UNVERIFIED]), so the
measured CPU rate stands in for the reference baseline (BASELINE.json
``published`` is empty; see BASELINE.md).

Methodology notes:
- Dispatch and fetch latency are excluded by the marginal-rate method:
  time K_small and K_large back-to-back dispatches (one tiny result fetch
  each) and divide the extra bytes by the extra time; median of REPS
  runs. Queued dispatches execute back-to-back on the chip, so the slope
  is pure chip throughput.
- The warmup doubles as the kernel correctness gate vs hashlib on every
  bench run (CPU-side validation is impractical: XLA:CPU needs >5 min to
  compile the unrolled kernel body -- see PERF.md).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# 256 KiB pieces x 1024-piece tiles = 256 MiB per dispatch: large enough
# that per-dispatch overhead vanishes in the slope, small enough that the
# K_LARGE queued executions' transient buffers fit HBM. SHA-256 work per
# byte is piece-length-invariant, so this measures the 4 MiB-piece rate too.
PIECE_LEN = int(os.environ.get("BENCH_PIECE_LEN", 256 * 1024))
CPU_BYTES = int(os.environ.get("BENCH_CPU_BYTES", 256 * 1024 * 1024))
K_SMALL = 4
K_LARGE = int(os.environ.get("BENCH_K_LARGE", 104))
REPS = int(os.environ.get("BENCH_REPS", 5))


def cpu_baseline_gbps() -> float:
    import hashlib

    data = np.random.default_rng(0).integers(
        0, 256, size=CPU_BYTES, dtype=np.uint8
    ).tobytes()
    t0 = time.perf_counter()
    view = memoryview(data)
    n = (len(view) + PIECE_LEN - 1) // PIECE_LEN
    for i in range(n):
        hashlib.sha256(view[i * PIECE_LEN : (i + 1) * PIECE_LEN]).digest()
    return len(data) / (time.perf_counter() - t0) / 1e9


def _marginal(dispatch, bytes_per_dispatch: int) -> float:
    """Median-of-REPS marginal rate of ``dispatch()`` (async, one fetch)."""

    def timed(k: int) -> float:
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = dispatch()
        _ = np.asarray(out[0, 0])  # forces the whole queued chain
        return time.perf_counter() - t0

    rates = []
    for _ in range(REPS):
        t_small, t_large = timed(K_SMALL), timed(K_LARGE)
        extra = (K_LARGE - K_SMALL) * bytes_per_dispatch
        rates.append(extra / max(t_large - t_small, 1e-9) / 1e9)
    rates.sort()
    return rates[len(rates) // 2]


def tpu_rates() -> tuple[float, float, float]:
    """(natural_gbps, packed_gbps, host_pack_gbps_core)."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from kraken_tpu.native import pack_tiles
    from kraken_tpu.ops.sha256 import _digest_bytes
    from kraken_tpu.ops.sha256_pallas import (
        N_TILE,
        hash_pieces_device,
        packed_nb,
        sha256_packed_tiles,
    )

    key = jax.random.PRNGKey(0)
    d = jax.random.bits(key, (N_TILE, PIECE_LEN), dtype=jnp.uint8)
    d.block_until_ready()
    host = np.asarray(d[:2])
    want = [hashlib.sha256(host[i].tobytes()).digest() for i in range(2)]

    # Natural path: warmup = correctness gate.
    warm = _digest_bytes(hash_pieces_device(d, PIECE_LEN)[:2])
    for i in range(2):
        assert warm[i].tobytes() == want[i], "natural kernel digest mismatch"
    natural = _marginal(
        lambda: hash_pieces_device(d, PIECE_LEN), N_TILE * PIECE_LEN
    )

    # Host packer rate (single core), then packed kernel path.
    host_all = np.asarray(d)
    nb = packed_nb(PIECE_LEN // 64)
    packed_np = np.zeros((1, nb, 16, 1024), dtype=np.uint32)
    t0 = time.perf_counter()
    pack_tiles(host_all, nb, packed_np)
    pack_gbps = host_all.nbytes / (time.perf_counter() - t0) / 1e9
    packed = jnp.asarray(packed_np.reshape(1, nb, 16, 8, 128))
    packed.block_until_ready()
    warm2 = _digest_bytes(sha256_packed_tiles(packed, PIECE_LEN // 64)[:2])
    for i in range(2):
        assert warm2[i].tobytes() == want[i], "packed kernel digest mismatch"
    packed_rate = _marginal(
        lambda: sha256_packed_tiles(packed, PIECE_LEN // 64),
        N_TILE * PIECE_LEN,
    )
    return natural, packed_rate, pack_gbps


def natural_chained_gbps() -> float:
    """Natural path, CHAINED: each dispatch's input folds in the previous
    digest, so every execution is distinct and data-dependent. This
    closes two holes the plain marginal method leaves open: a runtime
    that coalesces queued replays of identical executions, and latency
    jitter between the timing fences."""
    import jax
    import jax.numpy as jnp

    from kraken_tpu.ops.sha256 import _pad_block_for
    from kraken_tpu.ops.sha256_pallas import N_TILE, sha256_tiles

    pad = jnp.asarray(_pad_block_for(PIECE_LEN))

    @jax.jit
    def step(x):
        d = sha256_tiles(x, pad, PIECE_LEN // 64)
        first = jax.lax.bitcast_convert_type(d[0], jnp.uint8).reshape(-1)
        return jax.lax.dynamic_update_slice(x, first[None, :], (0, 0)), d

    x = jax.random.bits(
        jax.random.PRNGKey(0), (N_TILE, PIECE_LEN), dtype=jnp.uint8
    )
    x.block_until_ready()
    x, d = step(x)
    jax.block_until_ready((x, d))

    def timed(k: int, x):
        t0 = time.perf_counter()
        d = None
        for _ in range(k):
            x, d = step(x)
        np.asarray(d[0, 0])
        return time.perf_counter() - t0, x

    rates = []
    for _ in range(REPS):
        t_s, x = timed(K_SMALL, x)
        t_l, x = timed(K_LARGE, x)
        rates.append(
            (K_LARGE - K_SMALL) * N_TILE * PIECE_LEN
            / max(t_l - t_s, 1e-9) / 1e9
        )
    rates.sort()
    return rates[len(rates) // 2]


def cdc_gear_rate() -> float:
    """The dedup plane's Pallas gear kernel (ops/cdc_pallas.py), data
    resident, CHAINED (each dispatch folds the previous strict mask into
    its input) -- distinct data-dependent executions, immune to the
    replay-coalescing/jitter pathology natural_chained_gbps documents."""
    import jax
    import jax.numpy as jnp

    from kraken_tpu.ops.cdc import CDCParams
    from kraken_tpu.ops.cdc_pallas import _ROWS, _T_DISPATCH, _gear_pallas

    p = CDCParams()

    @jax.jit
    def step(x):
        strict, _loose = _gear_pallas(x, p.mask_strict, p.mask_loose)
        # One-row fold: enough to make every execution data-dependent
        # and distinct; a whole-batch fold would add ~2/3 extra HBM
        # traffic and measure the fold, not the kernel.
        x = jax.lax.dynamic_update_slice(x, strict[:, :1, :], (0, 0, 0))
        return x, strict

    x = jax.random.bits(
        jax.random.PRNGKey(0), (_T_DISPATCH, _ROWS, 128), dtype=jnp.uint8
    )
    x.block_until_ready()
    x, s = step(x)
    jax.block_until_ready((x, s))
    n = _T_DISPATCH * (1 << 18)

    def timed(k: int, x):
        t0 = time.perf_counter()
        s = None
        for _ in range(k):
            x, s = step(x)
        np.asarray(s[0, 0])
        return time.perf_counter() - t0, x

    rates = []
    # Chain lengths sized to THIS kernel's 64 MiB dispatch (vs the SHA
    # path's 256 MiB): 200 extra dispatches ≈ 13 GB per trial, enough to
    # dwarf jitter between the timing fences. REPS is shared with the
    # other measurements (BENCH_REPS).
    for _ in range(REPS):
        t_s, x = timed(2, x)
        t_l, x = timed(202, x)
        rates.append(200 * n / max(t_l - t_s, 1e-9) / 1e9)
    rates.sort()
    return rates[len(rates) // 2]


def data_plane_extras() -> dict:
    """Round-5 data-plane numbers folded into the headline line,
    best-effort: a failure here must NEVER break the primary metric
    (BENCH_EXTRAS=0 skips). Short configs -- the full sweeps live in
    bench_pair.py / bench_ingest.py."""
    if os.environ.get("BENCH_EXTRAS") == "0":
        return {}
    import asyncio
    import tempfile

    out: dict = {}
    try:
        from bench_pair import run_pair

        rates = []
        for _ in range(2):
            with tempfile.TemporaryDirectory(dir=".") as root:
                rates.append(
                    asyncio.run(run_pair(128, 1024, root))["goodput_mbps"]
                )
        out["pair_goodput_mbps"] = max(rates)
    except Exception as e:  # pragma: no cover - diagnostics only
        out["pair_goodput_error"] = repr(e)[:200]
    try:
        from bench_ingest import make_blob, run_ingest

        blob = make_blob(512)
        rates = []
        for _ in range(2):
            with tempfile.TemporaryDirectory(dir=".") as root:
                rates.append(asyncio.run(
                    run_ingest(blob, root, "cpu", "rename", 0)
                )["ingest_gbps"])
        out["origin_ingest_gbps"] = max(rates)
        rates = []
        for _ in range(2):
            with tempfile.TemporaryDirectory(dir=".") as root:
                rates.append(asyncio.run(run_ingest(
                    blob, root, "cpu", "rename", 0,
                    ingest={"window_bytes": 64 * 1024 * 1024,
                            "windows_in_flight": 2},
                ))["ingest_gbps"])
        out["origin_ingest_pipelined_gbps"] = max(rates)
    except Exception as e:  # pragma: no cover - diagnostics only
        out["origin_ingest_error"] = repr(e)[:200]
    return out


def main() -> None:
    cpu = None
    if os.environ.get("BENCH_SKIP_CPU") != "1":
        cpu = cpu_baseline_gbps()
    # BENCH_PROFILE=<dir>: wrap the TPU section in a jax.profiler trace
    # (XPlane + TensorBoard format) -- the SURVEY SS5 tracing plane for
    # the TPU side, alongside the swarm's networkevent JSONL.
    profile_dir = os.environ.get("BENCH_PROFILE", "")
    if profile_dir:
        import jax

        ctx = jax.profiler.trace(profile_dir)
    else:
        import contextlib

        ctx = contextlib.nullcontext()
    with ctx:
        natural, packed_rate, pack_gbps = tpu_rates()
        chained = natural_chained_gbps()
        cdc_gbps = cdc_gear_rate()
    extras = data_plane_extras()
    # Headline = the CHAINED number: every execution is distinct, so
    # neither replay coalescing nor fence jitter can inflate it; the
    # plain marginal rides along for cross-round comparability.
    headline = chained
    print(
        json.dumps(
            {
                "metric": "batched_sha256_metainfo_gen",
                "value": round(headline, 3),
                "unit": "GB/s/chip",
                "vs_baseline": round(headline / cpu, 3) if cpu else None,
                "natural_marginal_gbps": round(natural, 2),
                "natural_chained_gbps": round(chained, 2),
                "packed_kernel_gbps": round(packed_rate, 2),
                "host_pack_gbps_core": round(pack_gbps, 2),
                "cdc_gear_pallas_gbps": round(cdc_gbps, 2),
                **extras,
            }
        )
    )


if __name__ == "__main__":
    main()
