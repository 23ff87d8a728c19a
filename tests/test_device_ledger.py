"""The device-section ledger (core/hasher.py) and the queue stage in front
of it (core/ingest.py): whose work holds the chip, and who waits.

- the held/waited split on a fake clock, sections overlapping from several
  threads; a stress run on the real clock;
- every call site's ``purpose`` and ``kernel`` labels and its block counts;
- ``ingest_stage_seconds{stage="queue"}`` when a window waits for a worker;
- a window's ``hasher.device`` span under ``origin.ingest.commit``;
- device identity and peak memory through the registry's scrape hook.
"""

import asyncio
import os
import sys
import threading
import time

import numpy as np
import pytest

from kraken_tpu.core.hasher import (
    DEVICE_LEDGER,
    CPUPieceHasher,
    DeviceLedger,
    get_hasher,
    sha_blocks,
)
from kraken_tpu.utils.metrics import REGISTRY, Registry
from kraken_tpu.utils.trace import TRACER, TraceConfig

COUNTERS = (
    "sections", "rows", "blocks", "useful_blocks", "held_seconds",
    "wait_seconds", "first_use", "first_use_seconds",
)


def counts(registry, **labels) -> dict:
    return {
        name: registry.counter(f"hasher_device_{name}_total").value(**labels)
        for name in COUNTERS
    }


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def test_held_and_waited_on_a_fake_clock_across_threads():
    """Three threads enter at t=0, 1, 2 and their results come back at
    t=5, 7, 8 in that order: one chip serves them one after another."""
    clock, registry = FakeClock(), Registry()
    ledger = DeviceLedger(clock=clock, registry=registry)
    done: dict[str, tuple] = {}

    def run(name, rows, entered, leave):
        with ledger.section(
            "piece", "sha256_ragged", rows=rows, blocks=16,
            useful_blocks=rows * 10, payload_bytes=rows * 600,
        ) as sec:
            entered.set()
            assert leave.wait(10)
        done[name] = (clock.t - sec.held_s, clock.t, sec.waited_s)

    gates = {}
    for t, (name, rows) in enumerate((("a", 1), ("b", 1), ("c", 4))):
        clock.t = float(t)
        entered, leave = threading.Event(), threading.Event()
        th = threading.Thread(target=run, args=(name, rows, entered, leave))
        th.start()
        assert entered.wait(10)
        gates[name] = (th, leave)
    # Nothing has ended yet, and the chip has been held since t=0: a
    # reading in mid-flight counts the open sections up to the moment.
    clock.t = 3.0
    assert ledger.held_seconds() == 3.0
    for name, t in (("a", 5.0), ("b", 7.0), ("c", 8.0)):
        clock.t = t
        assert ledger.held_seconds() == t
        th, leave = gates[name]
        leave.set()
        th.join(10)
        assert not th.is_alive()

    assert done == {
        "a": (0.0, 5.0, 0.0), "b": (5.0, 7.0, 4.0), "c": (7.0, 8.0, 5.0),
    }
    got = counts(registry, purpose="piece", kernel="sha256_ragged")
    assert got["sections"] == 3 and got["rows"] == 6
    assert got["blocks"] == 6 * 16 and got["useful_blocks"] == 60
    assert got["held_seconds"] == 8.0  # the whole wall, and not more
    assert got["wait_seconds"] == 9.0
    # (1, 16) and (4, 16): two shapes, each first used once.
    assert got["first_use"] == 2 and got["first_use_seconds"] == 5.0 + 1.0
    clock.t = 20.0  # idle since t=8
    assert ledger.held_seconds() == 8.0


def test_a_section_that_finishes_out_of_order_keeps_held_disjoint():
    """hashlib workers really overlap: the later section comes back
    first. Held stays the union of the two, never their sum."""
    clock, registry = FakeClock(), Registry()
    ledger = DeviceLedger(clock=clock, registry=registry)
    outer = ledger.section("verify", "hashlib", rows=1, blocks=1,
                           useful_blocks=1, payload_bytes=1)
    result = {}

    def inner():
        with ledger.section("verify", "hashlib", rows=1, blocks=1,
                            useful_blocks=1, payload_bytes=1) as sec:
            clock.t = 4.0
        result["inner"] = (sec.held_s, sec.waited_s)

    outer.__enter__()  # t = 0
    clock.t = 1.0
    th = threading.Thread(target=inner)
    th.start()
    th.join(10)
    assert not th.is_alive()
    clock.t = 10.0
    outer.__exit__(None, None, None)
    assert result["inner"] == (3.0, 0.0)
    assert (outer.held_s, outer.waited_s) == (6.0, 4.0)
    assert ledger.held_seconds() == 9.0  # <= the wall of 10


def test_ledger_under_contention_loses_no_update():
    """More workers than cores on a short switch interval: every section
    is counted, nothing waited is negative, and held never passes the
    wall."""
    registry = Registry()
    ledger = DeviceLedger(registry=registry)
    workers, each = 4 * (os.cpu_count() or 2), 200
    waited_negative = []

    def work():
        for i in range(each):
            with ledger.section("chunk", "sha256_ragged", rows=1 + i % 3,
                                blocks=8, useful_blocks=5,
                                payload_bytes=300) as sec:
                pass
            if sec.waited_s < 0 or sec.held_s < 0:
                waited_negative.append((sec.waited_s, sec.held_s))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t0 = time.monotonic()
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        wall = time.monotonic() - t0
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    got = counts(registry, purpose="chunk", kernel="sha256_ragged")
    assert got["sections"] == workers * each
    assert got["useful_blocks"] == 5 * workers * each
    assert got["first_use"] == 3  # rows 1, 2, 3
    assert not waited_negative
    assert 0 <= got["held_seconds"] <= wall


# -- call sites -------------------------------------------------------------


def _jax_hasher():
    from kraken_tpu.ops.sha256 import JaxPieceHasher

    return JaxPieceHasher(use_pallas=False)


@pytest.mark.parametrize("size,blocks,useful", [
    (1000, 16, 16),    # 16 blocks with padding: an exact power of two
    (1100, 32, 18),    # 18 blocks scan as 32
])
def test_hash_pieces_is_a_piece_section_of_the_ragged_scan(size, blocks, useful):
    labels = {"purpose": "piece", "kernel": "sha256_ragged"}
    before = counts(REGISTRY, **labels)
    data = os.urandom(size)
    got = _jax_hasher().hash_pieces(data, 4 << 20)
    assert np.array_equal(got, CPUPieceHasher().hash_pieces(data, 4 << 20))
    d = delta(before, counts(REGISTRY, **labels))
    assert d["sections"] == 1 and d["rows"] == 1
    assert d["blocks"] == blocks and d["useful_blocks"] == useful
    assert d["useful_blocks"] <= d["blocks"]
    assert d["held_seconds"] > 0 and d["wait_seconds"] >= 0


def test_tile_rows_are_whole_tiles_of_the_kernel():
    from kraken_tpu.ops.sha256 import _tile_rows
    from kraken_tpu.ops.sha256_pallas import N_TILE

    assert [_tile_rows(r) for r in (1, N_TILE, N_TILE + 1)] == [
        N_TILE, N_TILE, 2 * N_TILE]


def test_full_pieces_are_one_section_over_every_sub_batch():
    from kraken_tpu.ops.sha256 import JaxPieceHasher

    plen = 4096
    hasher = JaxPieceHasher(sub_batch_bytes=4 * plen, use_pallas=False)
    labels = {"purpose": "piece", "kernel": "sha256_uniform"}
    before = counts(REGISTRY, **labels)
    tail_before = counts(REGISTRY, purpose="piece", kernel="sha256_ragged")
    data = os.urandom(6 * plen + 100)  # sub-batches of 4 and 2, and a tail
    got = hasher.hash_pieces(data, plen)
    assert np.array_equal(got, CPUPieceHasher().hash_pieces(data, plen))
    d = delta(before, counts(REGISTRY, **labels))
    per_piece = sha_blocks(plen)
    assert d["sections"] == 1 and d["rows"] == 4 + 2
    assert d["blocks"] == 6 * per_piece == d["useful_blocks"]
    tail = delta(tail_before,
                 counts(REGISTRY, purpose="piece", kernel="sha256_ragged"))
    assert tail["sections"] == 1 and tail["useful_blocks"] == sha_blocks(100)


def test_dedup_pass_is_a_chunk_section_with_its_own_stage_walls(tmp_path):
    from kraken_tpu.core.digest import Digest
    from kraken_tpu.ops.cdc import CDCParams
    from kraken_tpu.origin.dedup import DedupIndex
    from kraken_tpu.store import CAStore

    store = CAStore(str(tmp_path))
    blob = np.random.default_rng(3).integers(
        0, 256, size=40_000, dtype=np.uint8).tobytes()
    d = Digest.from_bytes(blob)
    uid = store.create_upload()
    store.write_upload_chunk(uid, 0, blob)
    store.commit_upload(uid, d)
    index = DedupIndex(store, hasher=_jax_hasher(),
                       params=CDCParams(2048, 8192, 32768))
    labels = {"purpose": "chunk", "kernel": "sha256_ragged"}
    before = counts(REGISTRY, **labels)
    verify_before = counts(REGISTRY, purpose="verify", kernel="sha256_ragged")
    sketch_before = counts(REGISTRY, purpose="sketch", kernel="minhash_sketch")
    stages = REGISTRY.histogram("dedup_stage_seconds")
    stage_before = {s: stages.count(stage=s)
                    for s in ("chunk", "hash", "sketch", "index")}
    record = index.add_blob_sync(d)
    d_chunk = delta(before, counts(REGISTRY, **labels))
    assert d_chunk["sections"] >= 1
    assert d_chunk["rows"] >= len(record.sizes)  # padded to a power of two
    assert d_chunk["useful_blocks"] == sum(
        sha_blocks(int(n)) for n in record.sizes)
    assert d_chunk["useful_blocks"] <= d_chunk["blocks"]
    assert delta(verify_before, counts(
        REGISTRY, purpose="verify", kernel="sha256_ragged"))["sections"] == 0
    assert delta(sketch_before, counts(
        REGISTRY, purpose="sketch", kernel="minhash_sketch"))["sections"] == 1
    for stage, n in stage_before.items():
        assert stages.count(stage=stage) == n + 1, stage


def test_verifier_is_a_verify_section():
    from kraken_tpu.p2p.storage import BatchedVerifier

    labels = {"purpose": "verify", "kernel": "sha256_ragged"}
    before = counts(REGISTRY, **labels)
    piece = os.urandom(3000)
    expected = CPUPieceHasher().hash_batch([piece])[0].tobytes()

    async def main():
        return await BatchedVerifier(_jax_hasher()).verify(piece, expected)

    assert asyncio.run(main()) is True
    d = delta(before, counts(REGISTRY, **labels))
    assert d["sections"] == 1 and d["useful_blocks"] == sha_blocks(3000)


def test_cpu_hasher_counts_the_same_sections_as_hashlib():
    before = counts(REGISTRY, purpose="piece", kernel="hashlib")
    chunk_before = counts(REGISTRY, purpose="chunk", kernel="hashlib")
    hasher = CPUPieceHasher()
    hasher.hash_pieces(os.urandom(10_000), 4096)
    hasher.hash_batch([os.urandom(100), os.urandom(5000)], purpose="chunk")
    d = delta(before, counts(REGISTRY, purpose="piece", kernel="hashlib"))
    assert d["sections"] == 1 and d["rows"] == 3
    assert d["useful_blocks"] == 2 * sha_blocks(4096) + sha_blocks(10_000 - 8192)
    assert d["useful_blocks"] <= d["blocks"] == 3 * sha_blocks(4096)
    d = delta(chunk_before, counts(REGISTRY, purpose="chunk", kernel="hashlib"))
    assert d["sections"] == 1 and d["rows"] == 2
    assert d["useful_blocks"] == sha_blocks(100) + sha_blocks(5000)


def test_cdc_and_score_kernels_run_inside_sections():
    from kraken_tpu.ops import cdc, minhash

    cdc_before = counts(REGISTRY, purpose="cdc", kernel="gear_candidates")
    data = os.urandom(50_000)
    assert cdc.chunk(data) == cdc.chunk_reference(data)
    d = delta(cdc_before, counts(REGISTRY, purpose="cdc", kernel="gear_candidates"))
    assert d["sections"] == 1 and d["blocks"] == 65536 // 64
    assert d["useful_blocks"] == -(-50_000 // 64)

    score_before = counts(REGISTRY, purpose="sketch", kernel="minhash_score")
    corpus = np.random.default_rng(0).integers(
        0, 1 << 32, size=(minhash._SCORE_DEVICE_MIN + 3, 16), dtype=np.uint32)
    scores = minhash._score(corpus[0], corpus)
    assert scores[0] == 1.0 and len(scores) == len(corpus)
    d = delta(score_before, counts(REGISTRY, purpose="sketch", kernel="minhash_score"))
    assert d["sections"] == 1 and d["useful_blocks"] == len(corpus) * 16
    assert d["useful_blocks"] <= d["blocks"]


# -- the queue in front of the device ----------------------------------------


def test_queue_stage_is_observed_when_a_window_waits_for_a_worker():
    """One executor serves every upload of the process: with both workers
    busy, a third session's window waits, and the wait is the queue
    stage's, not the hash stage's."""
    from kraken_tpu.core.hasher import PieceHasher
    from kraken_tpu.core.ingest import IngestConfig, IngestPipeline

    gate = threading.Event()
    entered = threading.Semaphore(0)

    class GatedHasher(PieceHasher):
        name = "cpu"

        def hash_pieces(self, data, piece_length):
            entered.release()
            assert gate.wait(10)
            return CPUPieceHasher().hash_pieces(data, piece_length)

    pipe = IngestPipeline(
        GatedHasher(), IngestConfig(window_bytes=1 << 20, windows_in_flight=2))
    stages = REGISTRY.histogram("ingest_stage_seconds")
    count0 = stages.count(stage="queue")
    sessions = [pipe.session(4096) for _ in range(3)]
    for ses in sessions:
        buf = ses.begin_window()
        buf[:5000] = os.urandom(5000)
        ses.submit(5000)
    for _ in range(2):  # both workers hold a window; the third queues
        assert entered.acquire(timeout=10)
    time.sleep(0.2)
    gate.set()
    for ses in sessions:
        assert ses.finish().shape == (2, 32)
    assert stages.count(stage="queue") == count0 + 3
    waits = sorted(ses.queue_seconds for ses in sessions)
    assert waits[2] >= 0.2 > waits[1]  # one window waited, two did not
    # Out of the overlap ratio: only the window's own stages are summed.
    assert "queue" not in sessions[0].stage_seconds
    assert "pack" not in sessions[0].stage_seconds


# -- one trace from the commit down to the device -----------------------------


@pytest.fixture
def sampled_tracer():
    cfg0, node0 = TRACER.config, TRACER.node
    TRACER.recorder.clear()
    TRACER.apply(TraceConfig(sample_rate=1.0))
    yield TRACER
    TRACER.config, TRACER.node = cfg0, node0
    TRACER.recorder.clear()


def test_device_span_is_a_descendant_of_the_commit(tmp_path, sampled_tracer):
    """The window is submitted at commit, runs on an executor thread, and
    its device section still joins the commit's trace."""
    from aiohttp import ClientSession

    from kraken_tpu.assembly import OriginNode
    from kraken_tpu.core.digest import Digest
    from kraken_tpu.origin.metainfogen import PieceLengthConfig

    blob = os.urandom(70_000)
    d = Digest.from_bytes(blob)

    async def main():
        node = OriginNode(
            store_root=str(tmp_path / "o"), dedup=False,
            piece_lengths=PieceLengthConfig(table=((0, 64 * 1024),)),
            ingest={"window_bytes": 1 << 20, "windows_in_flight": 2},
            trace={"sample_rate": 1.0},
        )
        await node.start()
        try:
            base = f"http://{node.addr}/namespace/ns/blobs/{d}"
            async with ClientSession() as http:
                async with http.post(f"{base}/uploads") as r:
                    uid = await r.text()
                async with http.patch(
                    f"{base}/uploads/{uid}", data=blob,
                    headers={"X-Upload-Offset": "0"},
                ) as r:
                    assert r.status == 204
                async with http.put(f"{base}/uploads/{uid}/commit") as r:
                    assert r.status == 201
        finally:
            await node.stop()

    join0 = REGISTRY.histogram("ingest_stage_seconds").count(stage="join")
    publish0 = REGISTRY.histogram("ingest_stage_seconds").count(stage="publish")
    asyncio.run(main())
    spans = sampled_tracer.recorder.snapshot()
    by_id = {s["span_id"]: s for s in spans}
    commit = [s for s in spans if s["name"] == "origin.ingest.commit"]
    assert len(commit) == 1
    assert "ingest_queue" in commit[0]["attrs"]
    device = [s for s in spans if s["name"] == "hasher.device"
              and s["trace_id"] == commit[0]["trace_id"]]
    assert device, [s["name"] for s in spans]
    for sp in device:
        assert sp["attrs"]["purpose"] == "piece"
        assert sp["attrs"]["kernel"] == "hashlib"  # a cpu origin
        assert {"start_mono", "held_s", "waited_s"} <= set(sp["attrs"])
        node = sp
        while node["span_id"] != commit[0]["span_id"]:
            node = by_id[node["parent_id"]]  # KeyError: the chain broke
    stages = REGISTRY.histogram("ingest_stage_seconds")
    assert stages.count(stage="join") == join0 + 1
    assert stages.count(stage="publish") == publish0 + 1


def test_hash_pool_submit_carries_the_callers_context(sampled_tracer):
    from kraken_tpu.core.hasher import HashPool
    from kraken_tpu.utils import trace

    pool = HashPool(1, name="ctx-test")
    with trace.span("parent") as parent:
        got = pool.submit(trace.current_ids).result(timeout=10)
    assert got == (parent.trace_id, parent.span_id)


# -- device identity and memory at scrape time --------------------------------


def test_scrape_hook_runs_on_render_and_cannot_fail_the_scrape():
    registry = Registry()
    gauge = registry.gauge("scraped_at_render")
    calls = []

    def hook():
        calls.append(1)
        gauge.set(len(calls))

    def broken():
        raise RuntimeError("no such device")

    registry.add_scrape_hook(broken)
    registry.add_scrape_hook(hook)
    assert "scraped_at_render 1.0" in registry.render()
    assert "scraped_at_render 2.0" in registry.render()


def test_device_hasher_exports_identity_and_peak_memory():
    import jax

    hasher = get_hasher("tpu")
    dev = jax.devices()[0]
    text = REGISTRY.render()
    assert (f'hasher_device_info{{count="1",kind="{dev.device_kind}",'
            f'platform="{dev.platform}"}} 1.0') in text
    assert 'hasher_device_memory_peak_bytes{hasher="tpu"}' in text
    assert hasher.devices() == [dev]
    assert DEVICE_LEDGER.held_seconds() >= 0


def test_idle_gap_is_booked_to_the_section_that_ends_it():
    """``hasher_device_idle_seconds_total``: the process's first section
    books no gap, a section that enters while another is open books none,
    and a gap is booked to the labels of the section that enters."""
    clock, registry = FakeClock(), Registry()
    ledger = DeviceLedger(clock=clock, registry=registry)
    idle = registry.counter("hasher_device_idle_seconds_total")

    def section(purpose, kernel):
        return ledger.section(purpose, kernel, rows=1, blocks=1,
                              useful_blocks=1, payload_bytes=64)

    clock.t = 10.0
    with section("verify", "sha256_tiles"):
        clock.t = 11.0
    assert idle.total() == 0  # nothing before the first section
    clock.t = 13.5
    with section("verify", "sha256_ragged_tiles"):
        clock.t = 14.0
        with section("piece", "sha256_tiles"):  # one is open: no gap
            clock.t = 15.0
        clock.t = 16.0
    assert idle.value(purpose="verify", kernel="sha256_ragged_tiles") == 2.5
    assert idle.value(purpose="piece", kernel="sha256_tiles") == 0
    clock.t = 20.0
    with section("piece", "sha256_tiles"):
        clock.t = 21.0
    assert idle.value(purpose="piece", kernel="sha256_tiles") == 4.0
    assert idle.value(purpose="verify", kernel="sha256_tiles") == 0
    assert idle.total() == 6.5
