"""Multi-host hash plane: the distributed backend over DCN.

The reference scales by adding origin hosts behind the hashring; its
communication plane is TCP + HTTP + Redis, with no NCCL/MPI analog
(uber/kraken, SURVEY.md SS2.7/SS5 -- upstream structure, unverified). The
TPU-native rebuild keeps that host-level story AND federates the hash
plane itself: ``jax.distributed`` joins every host's chips into one
global device set, each host hashes its LOCAL piece batch on its local
chips (piece bytes never cross hosts -- SHA-256 is embarrassingly
data-parallel and blob bytes live where the store put them), and the
[N, 8] digest matrix is exchanged with ONE global-mesh XLA collective:
32 B/piece riding DCN, exactly the control-plane-sized traffic the
scaling-book recipe says belongs on a cross-host axis.

On real TPU pods the same code rides ICI within a slice and DCN across
slices (the backend federates automatically); on CPU rigs -- including
this repo's tests -- the collective runs over gloo TCP, selected by
:func:`init_multihost`.

Hermetic self-test: ``python -m kraken_tpu.parallel.multihost <proc>
<nprocs> <port>`` (spawned N times by ``tests/test_multihost.py``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kraken_tpu.ops.sha256 import _digest_bytes
from kraken_tpu.parallel.hashplane import sharded_hash_pieces


def init_multihost(
    coordinator_address: str, num_processes: int, process_id: int
) -> None:
    """Join (or form) the multi-host cluster. Call once, before any other
    JAX use in the process.

    On CPU platforms this selects the gloo TCP collectives backend --
    without it the federated mesh forms but cross-host collectives have
    no transport. The setting is read only when a CPU client is created,
    so it is safe (and inert) on TPU platforms, which ship their own
    ICI/DCN transport. Nothing here may touch the backend before
    ``distributed.initialize`` -- client creation is what consumes the
    federation state.
    """
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


@dataclasses.dataclass(frozen=True)
class MultihostContext:
    """The federated device topology, one per joined process."""

    process_id: int
    num_processes: int
    hosts_mesh: Mesh       # one representative device per host ("hosts",)
    local_devices: tuple   # this host's own devices, id-sorted

    @classmethod
    def current(cls) -> "MultihostContext":
        devs = sorted(jax.devices(), key=lambda d: d.id)
        by_proc: dict[int, list] = {}
        for d in devs:
            by_proc.setdefault(d.process_index, []).append(d)
        reps = [by_proc[p][0] for p in sorted(by_proc)]
        return cls(
            process_id=jax.process_index(),
            num_processes=jax.process_count(),
            hosts_mesh=Mesh(np.array(reps), ("hosts",)),
            local_devices=tuple(by_proc[jax.process_index()]),
        )

    def local_mesh(self, axis: str = "pieces") -> Mesh:
        """This host's chips as a local data-parallel mesh -- the same
        shape :mod:`kraken_tpu.parallel.hashplane` shards over."""
        return Mesh(np.array(self.local_devices), (axis,))


def _allgather_digests(
    ctx: MultihostContext, words_local: np.ndarray
) -> list[np.ndarray]:
    """Exchange per-host [M_p, 8] digest-word matrices; returns one array
    per process, in process order, on every host.

    The exchange is a single jitted identity with replicated
    out-sharding over the ``hosts`` mesh -- XLA lowers it to an
    all-gather on the cross-host axis (gloo TCP here, DCN on pods).
    Ragged per-host counts ride a first tiny gather of the counts
    themselves, then rows pad to the max.
    """
    counts_local = np.array([[words_local.shape[0]]], dtype=np.int32)
    counts = np.asarray(_gather(ctx, counts_local, 1))[:, 0]
    m_max = int(counts.max()) if counts.size else 0
    padded = np.zeros((1, m_max, 8), dtype=np.uint32)
    padded[0, : words_local.shape[0]] = words_local
    gathered = np.asarray(_gather(ctx, padded, m_max))
    return [gathered[p, : counts[p]] for p in range(ctx.num_processes)]


@functools.lru_cache(maxsize=8)
def _replicate_fn(mesh: Mesh):
    """Compile-cached replicating identity for one hosts mesh. A fresh
    ``jax.jit(lambda x: x)`` per call would key the jit cache on a new
    function object every time -- every batch would recompile (and
    re-lower in lockstep on every host) the cross-host collective."""
    return jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))


def _gather(ctx: MultihostContext, local_block: np.ndarray, m: int):
    """All-gather ``local_block`` ([1, ...] per host) over the hosts mesh."""
    mesh = ctx.hosts_mesh
    spec = P("hosts", *([None] * (local_block.ndim - 1)))
    mine = [d for d in mesh.devices.flat if d.process_index == ctx.process_id]
    shard = jax.device_put(local_block, mine[0])
    global_shape = (ctx.num_processes,) + local_block.shape[1:]
    garr = jax.make_array_from_single_device_arrays(
        global_shape, NamedSharding(mesh, spec), [shard]
    )
    with mesh:
        out = _replicate_fn(mesh)(garr)
    return out


def multihost_hash_pieces(
    local_pieces: np.ndarray,
    piece_length: int,
    *,
    ctx: MultihostContext | None = None,
    use_pallas: bool | None = None,
) -> np.ndarray:
    """Hash this host's [M_local, piece_length] uint8 batch on its local
    chips and return the GLOBAL [sum_p M_p, 32] uint8 digest matrix
    (process order), replicated to every host.

    The compute is :func:`sharded_hash_pieces` over the local mesh (the
    production in-host path, unchanged); only the 32 B/piece digest
    matrix crosses hosts.
    """
    if ctx is None:
        ctx = MultihostContext.current()
    local_mesh = ctx.local_mesh()
    if use_pallas is None:
        use_pallas = ctx.local_devices[0].platform != "cpu"
    words = np.asarray(
        sharded_hash_pieces(
            local_mesh,
            local_pieces,
            piece_length,
            use_pallas=use_pallas,
            replicate=False,
        )
    )
    parts = _allgather_digests(ctx, words)
    return _digest_bytes(np.concatenate(parts, axis=0))


def _selftest(process_id: int, num_processes: int, port: int) -> None:
    """Joined by N subprocesses: every host hashes a distinct deterministic
    batch; each asserts the gathered global matrix equals hashlib over
    EVERY host's batch (recomputed locally -- no cross-checking channel
    besides the collective under test)."""
    import hashlib

    init_multihost(f"127.0.0.1:{port}", num_processes, process_id)
    ctx = MultihostContext.current()
    assert ctx.num_processes == num_processes, ctx

    piece_length = 256  # 4 sha blocks: fast under interpret/XLA-scan on CPU
    def batch_of(p: int) -> np.ndarray:
        rng = np.random.default_rng(1000 + p)
        m = 3 + p  # ragged counts exercise the count-gather path
        return rng.integers(0, 256, size=(m, piece_length), dtype=np.uint8)

    got = multihost_hash_pieces(batch_of(process_id), piece_length, ctx=ctx)
    want = np.concatenate(
        [
            np.stack(
                [
                    np.frombuffer(
                        hashlib.sha256(row.tobytes()).digest(), dtype=np.uint8
                    )
                    for row in batch_of(p)
                ]
            )
            for p in range(num_processes)
        ]
    )
    assert got.shape == want.shape, (got.shape, want.shape)
    assert (got == want).all(), "multihost digest mismatch"
    print(f"MULTIHOST-OK proc={process_id} digests={got.shape[0]}", flush=True)


if __name__ == "__main__":
    import sys

    _selftest(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
