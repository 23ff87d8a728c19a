"""Test session setup.

Tests run on a virtual 8-device CPU mesh so sharding/collective paths are
exercised without multi-chip hardware (the driver separately dry-runs the
multi-chip path; the chip check is chip_smoke.py). Must run before jax is
imported anywhere in the test process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# JAX_PLATFORMS above is only read when jax is first imported; pin the
# config too, so the session stays on the CPU even if something imported
# jax before this file ran. The suite never needs the chip (the chip
# check is chip_smoke.py) and several xdist workers could not share one.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import pytest  # noqa: E402

# Modules under the task-leak tripwire. Hedging and drain made
# cancellation the hot regression surface: a losing hedge or a drained
# conn that is cancelled but never reaped keeps pulling bytes (and
# holding buffers) forever, and asyncio.run's shutdown would silently
# cancel it -- hiding exactly the bug. These modules' asyncio.run calls
# get wrapped so the test FAILS if any task is still pending once the
# test body returns (short grace for in-flight done-callbacks).
# test_soak is the long-lived-fleet tier: a task leaked per soak cycle
# is exactly the weekly-OOM class the sentinel exists to catch, so the
# soak runs under the same tripwire.
_TASK_LEAK_MODULES = {"test_chaos", "test_degradation", "test_soak"}


# Suites running under the KT_SANITIZE asyncio sanitizer in tier-1:
# asyncio debug mode + the slow-sync-callback watchdog
# (kraken_tpu/utils/sanitize.py) that FAILS a test on any on-loop stall
# past the threshold, blaming the stack via the profiler's fold. The
# chaos + degradation suites are the loop's torture tier -- exactly
# where a blocking call regression would hide behind injected faults.
# KT_SANITIZE=1 extends it to every suite; KT_SANITIZE=0 force-disables
# (rig escape hatch); KT_SANITIZE_THRESHOLD tunes the stall bar.
_SANITIZE_MODULES = {"test_chaos", "test_degradation"}


@pytest.fixture(autouse=True)
def kt_sanitize(request, monkeypatch):
    import asyncio

    mode = os.environ.get("KT_SANITIZE", "")
    mod = request.module.__name__.rsplit(".", 1)[-1]
    enabled = mode == "1" or (mode != "0" and mod in _SANITIZE_MODULES)
    if not enabled:
        yield
        return

    from kraken_tpu.utils.sanitize import sanitized_run

    threshold = float(os.environ.get("KT_SANITIZE_THRESHOLD", "1.0"))
    violations: list = []
    orig_run = asyncio.run

    def sanitizing_run(coro, **kw):
        return sanitized_run(
            coro, threshold_seconds=threshold, violations=violations,
            _run=orig_run, **kw,
        )

    monkeypatch.setattr(asyncio, "run", sanitizing_run)
    yield
    assert not violations, (
        "KT_SANITIZE caught on-loop stalls (sync work on the event"
        " loop):\n" + "\n".join(v.render() for v in violations)
    )


@pytest.fixture
def tile_kernel_shapes(monkeypatch):
    """The uniform tile kernel only runs on the chip (chip_smoke.py holds
    it to hashlib there): a stand-in with the same contract takes its
    place and the fixture is the list of the shapes it was handed."""
    import jax.numpy as jnp

    from kraken_tpu.ops import sha256 as plane
    from kraken_tpu.ops import sha256_pallas

    shapes: list[tuple[int, int]] = []

    def tile_kernel(data_u8, piece_length, interpret=None):
        shapes.append(tuple(data_u8.shape))
        pad = jnp.asarray(plane._pad_block_for(piece_length))
        return plane._sha256_uniform(data_u8, pad, piece_length // 64)

    monkeypatch.setattr(sha256_pallas, "hash_pieces_device", tile_kernel)
    return shapes


@pytest.fixture(autouse=True)
def no_leaked_asyncio_tasks(request, monkeypatch):
    import asyncio

    mod = request.module.__name__.rsplit(".", 1)[-1]
    if mod not in _TASK_LEAK_MODULES:
        yield
        return
    leaks: list[str] = []
    orig_run = asyncio.run

    def checked_run(coro, **kw):
        async def wrapper():
            try:
                return await coro
            finally:
                cur = asyncio.current_task()
                pending: list = []
                for _ in range(40):  # ~2 s grace: reaping, not sleeping
                    pending = [
                        t for t in asyncio.all_tasks()
                        if t is not cur and not t.done()
                    ]
                    if not pending:
                        break
                    await asyncio.sleep(0.05)
                leaks.extend(
                    f"{t.get_name()}: {t.get_coro()!r}" for t in pending
                )
        return orig_run(wrapper(), **kw)

    monkeypatch.setattr(asyncio, "run", checked_run)
    yield
    assert not leaks, (
        "leaked pending asyncio tasks after test body:\n" + "\n".join(leaks)
    )


def pytest_configure(config):
    # Registered here (no pytest.ini exists): tier-1 is `-m 'not slow'`,
    # so the fast chaos subset runs in tier-1 and the soak subset does
    # not (docs/TESTING.md).
    config.addinivalue_line(
        "markers", "slow: soak-length tests excluded from tier-1"
    )
    config.addinivalue_line(
        "markers",
        "chaos: failpoint-driven failure injection (tests/test_chaos.py)",
    )
    config.addinivalue_line(
        "markers",
        "soak: gated multi-minute origin soak (tests/test_soak.py) --"
        " also requires KT_SOAK=1 (docs/TESTING.md)",
    )


def pytest_collection_modifyitems(config, items):
    # The gated soak tier: `soak`-marked tests need BOTH `-m slow` (they
    # are slow-marked too, so tier-1 never sees them) and the explicit
    # KT_SOAK=1 ack -- a bare `-m slow` run must not silently commit to
    # 5-10 minutes of wall.
    if os.environ.get("KT_SOAK") == "1":
        return
    skip = pytest.mark.skip(
        reason="gated soak: set KT_SOAK=1 (and run with -m slow)"
    )
    for item in items:
        if "soak" in item.keywords:
            item.add_marker(skip)
