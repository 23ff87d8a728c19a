"""The staging pool's contract per backing type (utils/bufpool.py).

Classes under ``MAP_CLASS`` are ``bytearray``s, classes from it up are
private anonymous mappings, so that a miss costs the pages the lease
touches and not a zero-fill of the whole class under the interpreter
lock. Every case below runs over one class of each kind: what a caller
may rely on does not depend on which it got.
"""

import mmap
import os
import pickle
import time

import numpy as np
import pytest

from kraken_tpu.utils.bufpool import MAP_CLASS, MIN_CLASS, BufferPool
from kraken_tpu.utils.metrics import REGISTRY

SMALL = 16 * MIN_CLASS  # 64 KiB: a bytearray class
LARGE = 4 * MAP_CLASS  # 4 MiB, a shipped piece: a mapped class

both_kinds = pytest.mark.parametrize(
    "size, backing",
    [(SMALL, bytearray), (LARGE, mmap.mmap)],
    ids=["small-bytearray", "large-mapping"],
)


def _pool(request, **kw) -> BufferPool:
    # One label a test: the registry's series outlive the pool.
    return BufferPool(name=request.node.name, **kw)


@both_kinds
def test_miss_is_a_zeroed_writable_buffer_of_the_class(request, size, backing):
    pool = _pool(request)
    lease = pool.lease(size - 100)  # rounds up to the class
    assert type(lease._buf) is backing and len(lease._buf) == size
    assert len(lease.view) == size - 100 and not lease.view.readonly
    assert not np.frombuffer(lease._buf, dtype=np.uint8).any()
    lease.view[-4:] = b"tail"
    assert bytes(lease._buf[size - 104 : size - 100]) == b"tail"
    assert (pool.misses, pool.hits, pool.allocated) == (1, 0, 1)
    lease.release()
    assert pool.leased == 0


@both_kinds
def test_release_then_lease_is_a_hit_on_the_same_backing(request, size, backing):
    pool = _pool(request)
    first = pool.lease(size)
    buf = first._buf
    first.view[:3] = b"abc"
    first.release()
    assert pool.retained_bytes == size
    second = pool.lease(size // 2 + 1)  # same class, another length
    assert second._buf is buf
    assert (pool.misses, pool.hits, pool.allocated) == (1, 1, 1)
    assert pool.retained_bytes == 0
    second.view[:3] = b"xyz"  # recycled memory is live memory
    assert bytes(buf[:3]) == b"xyz"
    second.release()


@both_kinds
def test_release_under_a_reader_drops_and_never_recycles(request, size, backing):
    """The cancelled-waiter race: a hash thread still exports the view
    when the lease ends (``hashlib`` holds the buffer for the length of
    an ``update``; a ``PickleBuffer`` holds it the same way for as long
    as the test likes). The buffer is neither pooled nor torn down under
    the reader -- a mapping above all, where a close would turn the
    reader's next access into a fault."""
    pool = _pool(request)
    lease = pool.lease(size)
    buf = lease._buf
    lease.view[:6] = b"reader"
    reader = pickle.PickleBuffer(lease.view)
    lease.release()
    lease.release()  # idempotent on this path too
    assert lease.released and pool.leased == 0
    assert pool.retained_bytes == 0
    assert bytes(reader.raw()[:6]) == b"reader"  # still mapped, still ours
    if backing is mmap.mmap:
        assert not buf.closed
    nxt = pool.lease(size)
    assert nxt._buf is not buf and pool.misses == 2
    nxt.view[:6] = b"writer"
    assert bytes(reader.raw()[:6]) == b"reader"
    nxt.release()
    reader.release()


@both_kinds
def test_over_budget_is_not_retained_and_returns_its_memory(request, size, backing):
    pool = _pool(request, budget_bytes=size)
    a, b = pool.lease(size), pool.lease(size)
    kept, spilled = a._buf, b._buf
    a.release()
    b.release()
    assert pool.leased == 0 and pool.retained_bytes == size
    if backing is mmap.mmap:
        assert spilled.closed and not kept.closed  # unmapped, not parked
    again = pool.lease(size)
    assert again._buf is kept
    again.release()


def test_over_budget_mapping_under_a_live_slice_goes_with_the_slice(request):
    """The ingest worker's frame holds ``lease.view[:nbytes]`` while its
    ``finally`` releases the lease: the unmap cannot happen there, and
    must not be attempted again on memory a reader holds."""
    pool = _pool(request, budget_bytes=0)
    lease = pool.lease(LARGE)
    buf = lease._buf
    held = lease.view[:4096]
    held[:4] = b"held"
    lease.release()
    assert pool.leased == 0 and pool.retained_bytes == 0
    assert not buf.closed and bytes(held[:4]) == b"held"
    held.release()
    buf.close()  # the last view is gone: nothing holds the pages


@both_kinds
def test_miss_bytes_counter_moves_by_the_class_a_miss(request, size, backing):
    pool = _pool(request)
    counter = REGISTRY.counter("bufpool_miss_bytes_total")
    name = request.node.name
    assert counter.value(pool=name) == 0

    a = pool.lease(size - 1)
    assert counter.value(pool=name) == size == pool.miss_bytes
    b = pool.lease(1 + size // 2)  # pool empty: a second miss of the class
    assert counter.value(pool=name) == 2 * size
    a.release()
    b.release()
    for _ in range(3):  # hits ask the allocator for nothing
        pool.lease(size).release()
    assert pool.hits == 3 and counter.value(pool=name) == 2 * size
    ratio = REGISTRY.gauge("bufpool_hit_ratio").value(pool=name)
    assert ratio == pytest.approx(3 / 5)


@pytest.mark.filterwarnings("ignore:This process .* is multi-threaded")
def test_forked_child_gets_its_own_copy_of_a_retained_mapping(request):
    """A leech shard forks with the wire pool's free list in it: a
    retained buffer written on both sides of the fork must be two
    buffers, as the heap's were (MAP_SHARED would make it one). The
    child touches the buffer and nothing that takes a lock."""
    pool = _pool(request)
    lease = pool.lease(LARGE)
    buf = lease._buf
    lease.view[:6] = b"parent"
    lease.release()
    assert pool.retained_bytes == LARGE
    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:
        code = 1
        try:
            seen = buf[:6]
            buf[:6] = b"child!"
            os.write(w, seen)
            code = 0
        finally:
            os._exit(code)
    os.close(w)
    try:
        seen = os.read(r, 16)
    finally:
        os.close(r)
        _, status = os.waitpid(pid, 0)
    assert status == 0 and seen == b"parent"
    again = pool.lease(LARGE)
    assert again._buf is buf and bytes(again.view[:6]) == b"parent"
    again.release()


def test_a_window_sized_miss_costs_what_it_touches():
    """The cost itself: a miss of the ingest plane's shipped 64 MiB
    window that lands 4 KiB, release included, against what the same
    miss cost as ``bytearray(64 << 20)`` (allocate + zero-fill with the
    interpreter lock held: tens of milliseconds against microseconds,
    so a tenth cannot flake). Best of three each, same machine, same
    test."""
    window = 64 << 20
    pool = BufferPool(budget_bytes=0, name="cost")  # every lease a miss
    page = b"\xa5" * 4096

    def lease_touch_release() -> float:
        t0 = time.perf_counter()
        lease = pool.lease(window)
        lease.view[:4096] = page
        lease.release()
        return time.perf_counter() - t0

    def zero_filled() -> float:
        t0 = time.perf_counter()
        buf = bytearray(window)
        buf[:4096] = page
        del buf
        return time.perf_counter() - t0

    ours = min(lease_touch_release() for _ in range(3))
    theirs = min(zero_filled() for _ in range(3))
    assert pool.misses == 3 and pool.miss_bytes == 3 * window
    assert ours < theirs / 10, (ours, theirs)
