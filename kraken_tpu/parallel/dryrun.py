"""Hermetic multi-chip dry-run body (run me with JAX_PLATFORMS=cpu).

This module is the subprocess target of ``__graft_entry__.dryrun_multichip``.
It self-provisions ``n`` virtual CPU devices and validates the production
sharding: the piece batch data-parallel across the ``pieces`` mesh axis,
digests all-gathered to every chip (SURVEY.md SS2.7).

Hermeticity contract (the round-2 driver gate failed on both axes):

1. **Device count** does not depend on anyone exporting ``XLA_FLAGS``:
   before backend init we set ``jax.config.jax_num_cpu_devices`` (and the
   spawning parent also exports the XLA flag, belt and braces).
2. **Zero eager work on the default device**: the platform is pinned to
   ``cpu`` before first device query (so a version-skewed real accelerator
   is never initialised), and the body runs under
   ``jax.transfer_guard_host_to_device("disallow")`` so any stray implicit
   default-device placement (the r02 ``convert_element_type`` escape) is a
   hard error rather than a silent TPU touch.
"""

from __future__ import annotations

import os
import sys


def run_dryrun(n_devices: int) -> None:
    """Provision ``n_devices`` virtual CPU devices and run one sharded step."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax

    # setdefault above loses to a caller's JAX_PLATFORMS; the dry-run must
    # stay on the CPU whatever the environment says.
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)

    import hashlib

    import numpy as np

    from kraken_tpu.ops.sha256 import _digest_bytes
    from kraken_tpu.parallel import piece_mesh, sharded_hash_pieces

    devices = jax.devices()
    assert all(d.platform == "cpu" for d in devices), devices
    assert len(devices) >= n_devices, (
        f"self-provisioning failed: need {n_devices} cpu devices, "
        f"have {len(devices)}"
    )

    mesh = piece_mesh(n_devices, platform="cpu")

    piece_len = 256  # tiny: 4 SHA blocks per piece
    n = 4 * n_devices + 1  # deliberately ragged vs the device quantum
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(n, piece_len), dtype=np.uint8)
    want = [hashlib.sha256(data[i].tobytes()).digest() for i in range(n)]

    # Pallas is deliberately NOT run here: XLA:CPU takes >5 min to compile
    # its ~6k-op unrolled round body in any CPU mode (measured 2026-07-29);
    # its correctness home is the real chip (entry() + chip_smoke.py's
    # digests against hashlib). The XLA-scan path exercises the identical
    # shard_map + all-gather sharding.
    with jax.transfer_guard_host_to_device("disallow"):
        out = sharded_hash_pieces(
            mesh,
            data,
            piece_len,
            use_pallas=False,
            replicate=True,
        )
        out.block_until_ready()
    assert out.shape == (n, 8), out.shape
    assert out.sharding.is_fully_replicated, "digest gather missing"
    got = _digest_bytes(out)
    for i in range(n):
        assert got[i].tobytes() == want[i], (
            f"multi-chip digest mismatch vs hashlib (piece {i})"
        )


if __name__ == "__main__":
    run_dryrun(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
    print("dryrun ok")
