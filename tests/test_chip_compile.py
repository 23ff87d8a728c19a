"""The served path's device programs, compiled for a TPU v5e that is
described and not attached (no chip needed; nothing runs).

What interpret mode and the CPU backend cannot show: whether the chip's
compiler accepts each kernel at the SHIPPED shapes, and how much device
memory the program takes there. The shapes are the ones the served path
dispatches with the shipped config: a 64 MiB ingest window
(config/origin/base.yaml ``ingest.window_bytes``) of 4, 8 or 16 MiB pieces
(origin/metainfogen.py PieceLengthConfig). A compile that passes is not a
chip run -- results and times come from ``chip_smoke.py`` on the chip.

All of these live in this one file, and the topology is described inside a
fixture: only one process at a time may load the TPU's library, so nothing
here may touch it while a module is imported or collected.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

MIB = 1 << 20
WINDOW = 64 * MIB  # config/origin/base.yaml ingest.window_bytes
HBM = int(15.75 * (1 << 30))  # what the v5e's compiler says it has


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without the chip: the next compile would
    warn and compile again. Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize(
    "rows,piece_mib",
    [
        pytest.param(16, 4, id="window-4MiB"),
        pytest.param(8, 8, id="window-8MiB"),
        pytest.param(4, 16, id="window-16MiB"),
        pytest.param(64, 4, id="verify-batch-64x4MiB"),
        pytest.param(1024, 4, id="whole-tile-1024x4MiB"),
    ],
)
def test_tile_kernel_fits_the_chip_at_shipped_batches(one_chip, rows, piece_mib):
    """The natural-layout SHA kernel at the batches the served path hands
    it: one shipped 64 MiB window per piece tier (JaxPieceHasher.
    hash_pieces), the agent's largest verify batch (hash_batch, bounded
    by sub_batch_bytes) and a whole 1024-row tile, where no lane rides
    the edge block. Device memory is of the order of the batch.
    Padding the rows up to the kernel's 1024-piece tile took 1024 x
    piece_length of temp -- 4 GiB, 8 GiB, and more than the chip has at
    16 MiB pieces."""
    from kraken_tpu.ops.sha256_pallas import hash_pieces_device

    plen = piece_mib * MIB
    x = jax.ShapeDtypeStruct((rows, plen), jnp.uint8, sharding=one_chip)
    compiled = _compile(
        lambda d: hash_pieces_device(d, plen, interpret=False), x
    )
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.temp_size_in_bytes <= 2 * rows * plen, mem
    assert mem.argument_size_in_bytes <= 2 * rows * plen, mem


def _equations(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr inside its equations'
    parameters (a pallas_call's kernel, a loop's body, a cond's arms)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _equations(sub)
    return n


# What the kernel whose block loop was unrolled traced at this window: nine
# compressions, 7-8 s of Python a shape a start on an idle chip machine
# with the interpreter lock held, 1.2-1.5 s rolled (PERF.md SS5, PR 34).
UNROLLED_EQUATIONS = 32_259


def test_tile_kernel_traces_one_compression_a_shape():
    """What a uniform shape costs a start is tracing and lowering, and that
    is as long as what is traced: the block loop rolled over words parked
    in VMEM is one ``_rounds64`` and the relayout, whatever the chain's
    length. No clock: the count of equations at the shipped window."""
    from kraken_tpu.ops.sha256_pallas import hash_pieces_device

    plen = 4 * MIB
    x = jax.ShapeDtypeStruct((WINDOW // plen, plen), jnp.uint8)
    traced = jax.jit(
        lambda d: hash_pieces_device(d, plen, interpret=False)
    ).trace(x)
    got = _equations(traced.jaxpr.jaxpr)
    limit = UNROLLED_EQUATIONS // 3
    assert got <= limit, (
        f"sha256_tiles traces {got} equations at 16 x 4 MiB; the limit is "
        f"{limit}, a third of the {UNROLLED_EQUATIONS} its unrolled block "
        f"loop traced"
    )


def _ragged_slab_shapes(lanes, slab, sharding=None):
    """What ``sha256_ragged_slab`` is handed at the compiled shape
    (lanes, slab blocks): state, data, per-lane counts, scalars."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (
        sds((8, 8, 128), jnp.uint32), sds((lanes, slab * 64), jnp.uint8),
        sds((8, 128), jnp.int32), sds((2,), jnp.int32),
    )


@pytest.mark.parametrize("shape", ["RAGGED_ROW_SHAPE", "RAGGED_TILE_SHAPE"])
def test_ragged_tile_kernel_fits_the_chip_at_each_compiled_shape(
    one_chip, shape
):
    """The ragged tile kernel at the two shapes the served path
    dispatches (one row, or a tile of 1024, a slab of the block axis a
    call). Device memory is the slab, the state and the counts: the
    chain's length is not in the program."""
    from kraken_tpu.ops import sha256_pallas

    lanes, slab = getattr(sha256_pallas, shape)
    compiled = sha256_pallas.sha256_ragged_slab.lower(
        *_ragged_slab_shapes(lanes, slab, one_chip), interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # A one-row u8 slab is laid out in tiles of several rows on the device.
    slab_bytes = max(lanes, 32) * slab * 64
    assert mem.temp_size_in_bytes <= slab_bytes, mem
    assert mem.argument_size_in_bytes <= 2 * slab_bytes + (64 << 10), mem
    assert mem.alias_size_in_bytes == 8 * 8 * 128 * 4, mem  # state in place


def test_ragged_batches_of_any_length_and_row_count_share_two_programs(
    monkeypatch,
):
    """The compile key: two batches that differ in every length and in
    their row counts hand ``sha256_ragged_slab`` the same shapes, and
    those are the two compiled above. A shape met first under load is
    a stall of seconds with the GIL held (PERF.md SS6, PR 21)."""
    import os

    import numpy as np

    from kraken_tpu.ops import sha256 as plane
    from kraken_tpu.ops import sha256_pallas

    seen = []

    def record(state, data, nblocks, scalars, interpret=None):
        seen[-1].add(tuple(
            (tuple(a.shape), np.dtype(a.dtype).name)
            for a in (state, data, nblocks, scalars)
        ))
        return state

    monkeypatch.setattr(sha256_pallas, "sha256_ragged_slab", record)
    h = plane.JaxPieceHasher(use_pallas=True)
    for lengths in (
        [1, 70_000, (1 << 20) + 3] + [5000 + 37 * i for i in range(40)],
        [0, 33, (1 << 22) + 1] + [10_001 + 200 * i for i in range(200)],
    ):
        seen.append(set())
        h.hash_batch([os.urandom(n) for n in lengths])
    want = {
        tuple((s.shape, np.dtype(s.dtype).name)
              for s in _ragged_slab_shapes(lanes, slab))
        for lanes, slab in (
            sha256_pallas.RAGGED_ROW_SHAPE, sha256_pallas.RAGGED_TILE_SHAPE
        )
    }
    assert seen[0] == seen[1] == want


def test_ragged_scan_compiles_at_a_verify_batch(one_chip):
    """The ragged scan at 16 rows of (4 MiB + SHA padding), block count
    bucketed to the next power of two (ops/sha256.py _hash_batch_raw):
    where a verify batch goes when its pieces are not of one length."""
    from kraken_tpu.ops.sha256 import _sha256_ragged

    blocks = jax.ShapeDtypeStruct(
        (16, 131072, 64), jnp.uint8, sharding=one_chip
    )
    nblocks = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    mem = _compile(_sha256_ragged, blocks, nblocks).memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < HBM // 2, mem


def test_gear_kernel_compiles_at_dispatch_size(one_chip):
    from kraken_tpu.ops.cdc import CDCParams
    from kraken_tpu.ops.cdc_pallas import _ROWS, _T_DISPATCH, _gear_pallas

    p = CDCParams()
    segs = jax.ShapeDtypeStruct(
        (_T_DISPATCH, _ROWS, 128), jnp.uint8, sharding=one_chip
    )
    compiled = _compile(
        lambda s: _gear_pallas(s, p.mask_strict, p.mask_loose), segs
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_window_hash_compiles_for_four_chips(topo):
    """``hasher: tpu-sharded``: one shipped window row-sharded over the
    four-chip host's mesh. Each chip runs the Pallas kernel on its own
    rows; nothing crosses chips and no chip pads its 4 rows to a tile."""
    from kraken_tpu.parallel.hashplane import _sharded_fn

    import numpy as np

    mesh = Mesh(np.asarray(topo.devices), ("pieces",))
    plen = 4 * MIB
    x = jax.ShapeDtypeStruct(
        (WINDOW // plen, plen), jnp.uint8,
        sharding=NamedSharding(mesh, P("pieces", None)),
    )
    pad = jax.ShapeDtypeStruct(
        (16,), jnp.uint32, sharding=NamedSharding(mesh, P())
    )
    fn = _sharded_fn(mesh, plen // 64, True, False, False)
    compiled = fn.lower(x, pad).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text
    mem = compiled.memory_analysis()  # per device
    assert mem.temp_size_in_bytes <= 2 * WINDOW, mem
    assert mem.argument_size_in_bytes <= WINDOW, mem
