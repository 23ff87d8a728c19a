"""The push-step ledger (utils/pushsteps.py) and the process's own bill
(utils/metrics.py collect_process): what a push costs the origin's
interpreter, step by step. CPU, no herd: one in-process origin."""

import asyncio
import builtins
import os
import threading

import pytest
from aiohttp import ClientSession

from kraken_tpu.assembly import OriginNode
from kraken_tpu.backend import Manager as BackendManager
from kraken_tpu.core.digest import Digest
from kraken_tpu.origin.metainfogen import PieceLengthConfig
from kraken_tpu.utils.metrics import REGISTRY, Registry, collect_process
from kraken_tpu.utils.pushsteps import (
    PUSH_STEPS, StepLedger, install, push_step_table,
)


class Clocks:
    """A wall and a cpu clock that move only when told to."""

    def __init__(self):
        self.wall_now = 100.0
        self.cpu_now = 5.0
        self.reads = 0

    def run(self, wall: float, cpu: float) -> None:
        self.wall_now += wall
        self.cpu_now += cpu

    def wall(self) -> float:
        self.reads += 1
        return self.wall_now

    def cpu(self) -> float:
        self.reads += 1
        return self.cpu_now

    def ledger(self, every: int = 1) -> tuple[StepLedger, Registry]:
        reg = Registry()
        return StepLedger(self.wall, self.cpu, reg, every=every), reg


def _sample(reg: Registry, family: str, **labels) -> float | None:
    want = {f'{k}="{v}"' for k, v in labels.items()}
    for line in reg.render().splitlines():
        name, _, rest = line.partition("{")
        if name == family and want <= set(rest.split("}")[0].split(",")):
            return float(line.rsplit(" ", 1)[1])
    return None


def _books(ledger: StepLedger) -> dict:
    """(step, thread class) -> (entries of the step, wall, cpu)."""
    entries, seconds = ledger.totals()
    return {key: (entries[key[0]], *times) for key, times in seconds.items()}


def test_step_books_wall_and_cpu_under_their_labels():
    clocks = Clocks()
    ledger, reg = clocks.ledger()
    with ledger.step("create"):
        clocks.run(wall=0.5, cpu=0.125)
    with ledger.step("create"):
        clocks.run(wall=0.25, cpu=0.25)
    seconds = "origin_push_step_seconds_total"
    assert _sample(reg, seconds, step="create", clock="wall") == 0.75
    assert _sample(reg, seconds, step="create", clock="cpu") == 0.375
    assert _sample(reg, "origin_push_steps_total", step="create") == 2
    # The booking thread's class is a label: this test runs on MainThread.
    assert _sample(reg, seconds, step="create", clock="cpu",
                   **{"class": "loop"}) == 0.375


def test_step_that_raises_is_booked():
    """Unlike timed_stage, which bills only what succeeds: the lock was
    held either way."""
    clocks = Clocks()
    ledger, reg = clocks.ledger()
    with pytest.raises(ValueError):
        with ledger.step("patch.open"):
            clocks.run(wall=0.5, cpu=0.25)
            raise ValueError("no such upload")
    assert _books(ledger) == {("patch.open", "loop"): (1, 0.5, 0.25)}


def test_nested_steps_do_not_double_book():
    clocks = Clocks()
    ledger, _ = clocks.ledger()
    with ledger.step("patch.flush"):
        clocks.run(wall=1.0, cpu=0.5)
        with ledger.step("patch.journal"):
            clocks.run(wall=2.0, cpu=0.25)
            with pytest.raises(OSError):
                with ledger.step("fsync"):
                    clocks.run(wall=4.0, cpu=0.125)
                    raise OSError("disk")
        clocks.run(wall=8.0, cpu=1.0)
    books = _books(ledger)
    assert books == {
        ("fsync", "loop"): (1, 4.0, 0.125),
        ("patch.journal", "loop"): (1, 2.0, 0.25),
        ("patch.flush", "loop"): (1, 9.0, 1.5),
    }
    # The cpu clock's whole run, once.
    assert sum(row[2] for row in books.values()) == 1.875


def test_one_stretch_in_a_few_is_clocked_and_every_entry_counted(monkeypatch):
    """The clocks are read for one outermost frame in ``every`` and for
    what is opened inside it, for no other; the seconds are the clocked
    frames' times ``every``, the entries are exact."""
    import random

    clocks = Clocks()
    ledger, _ = clocks.ledger(every=4)
    draws = iter([0.9, 0.1, 0.5, 0.3] * 3)  # the second stretch of four
    monkeypatch.setattr(random, "random", lambda: next(draws))

    @ledger.stepped("commit.rest")
    async def handler():
        clocks.run(wall=1.0, cpu=0.5)
        with ledger.step("commit.pin"):  # no draw of its own
            clocks.run(wall=2.0, cpu=0.25)

    for i in range(12):
        before = clocks.reads
        asyncio.run(handler())
        assert clocks.reads - before == (8 if i % 4 == 1 else 0)
    assert _books(ledger) == {
        ("commit.rest", "loop"): (12, 12.0, 6.0),
        ("commit.pin", "loop"): (12, 24.0, 3.0),
    }


def test_call_runs_on_the_worker_and_the_await_is_wall_only():
    clocks = Clocks()
    ledger, reg = clocks.ledger()

    def work():
        clocks.run(wall=0.5, cpu=0.25)
        return threading.current_thread().name

    async def main():
        return await ledger.awaited(
            "commit.rename",
            asyncio.to_thread(ledger.call, "commit.rename", work),
        )

    assert asyncio.run(main()).startswith("asyncio_")
    assert _books(ledger) == {
        ("commit.rename", "worker"): (1, 0.5, 0.25),
        ("commit.rename.await", "loop"): (1, 0.5, None),
    }
    assert _sample(reg, "origin_push_step_seconds_total",
                   step="commit.rename.await", clock="cpu") is None


def test_coroutine_is_booked_slice_by_slice():
    """What a coroutine costs its thread: its slices, not what it waits
    for; a step inside a slice comes off it; a cancellation reaches it."""
    clocks = Clocks()
    ledger, _ = clocks.ledger()
    seen = []

    @ledger.stepped("commit.rest")
    async def handler():
        clocks.run(wall=1.0, cpu=0.5)
        await asyncio.sleep(0)  # another task's turn: not this one's time
        with ledger.step("commit.pin"):
            clocks.run(wall=2.0, cpu=0.25)
        try:
            await asyncio.sleep(30)
        except asyncio.CancelledError:
            seen.append("cancelled")
            clocks.run(wall=4.0, cpu=0.125)
            raise

    async def other():
        clocks.run(wall=64.0, cpu=32.0)

    async def main():
        task = asyncio.create_task(handler())
        await asyncio.gather(other(), asyncio.sleep(0.01))
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    asyncio.run(main())
    assert seen == ["cancelled"]
    assert _books(ledger) == {
        ("commit.pin", "loop"): (1, 2.0, 0.25),
        ("commit.rest", "loop"): (1, 5.0, 0.625),
    }


def test_install_names_the_pool_calls_that_are_no_step():
    clocks = Clocks()
    ledger, _ = clocks.ledger()

    def read_sidecar():
        clocks.run(wall=0.5, cpu=0.25)

    @ledger.stepped("metainfo.read")
    def get_cached():
        clocks.run(wall=1.0, cpu=0.5)
        return threading.current_thread().name

    async def main():
        install(asyncio.get_running_loop(), ledger)
        await asyncio.to_thread(read_sidecar)
        await asyncio.to_thread(ledger.call, "patch.flush", read_sidecar)
        return await asyncio.to_thread(get_cached)

    # The pool is asyncio's own by its threads' names.
    assert asyncio.run(main()).startswith("asyncio_")
    name = "test_install_names_the_pool_calls_that_are_no_step.<locals>."
    assert _books(ledger) == {
        ("thread." + name + "read_sidecar", "worker"): (1, 0.5, 0.25),
        ("patch.flush", "worker"): (1, 0.5, 0.25),
        ("metainfo.read", "worker"): (1, 1.0, 0.5),
    }


def test_books_from_many_threads_lose_nothing():
    """More bookers than cores on a short switch interval: a lost update
    would leave an entry or a second out."""
    import sys

    ledger, reg = Clocks().ledger()
    threads, each = 4 * (os.cpu_count() or 2), 2000

    def book():
        for _ in range(each):
            ledger.book("create", 1.0, 0.5)
            ledger.count("create")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=book) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(interval)
    n = threads * each
    assert _books(ledger) == {("create", "other"): (n, n * 1.0, n * 0.5)}
    assert _sample(reg, "origin_push_steps_total", step="create") == n


# -- one push through an in-process origin ------------------------------------

PIECE = 64 * 1024
ONCE_A_PUSH = (
    "create", "create.rest",
    "commit.size", "commit.join", "commit.join.await", "commit.rename",
    "commit.rename.await", "commit.adopt", "commit.adopt.await",
    "commit.namespace", "commit.namespace.await", "commit.seed", "commit.pin",
    "commit.retry_add", "commit.replicate", "commit.dedup_schedule",
    "commit.rest", "metainfo.read", "metainfo.read.await", "metainfo.rest",
)
ONCE_A_PATCH = (
    "patch.open", "patch.flush", "patch.flush.await", "patch.journal",
    "patch.close", "patch.rest",
)


def _entries() -> dict[str, int]:
    return PUSH_STEPS.totals()[0]


def _cpu() -> float:
    return sum(cpu or 0.0 for _, cpu in PUSH_STEPS.totals()[1].values())


async def _push(node: OriginNode, blob: bytes, patches: int) -> None:
    d = Digest.from_bytes(blob)
    base = f"http://{node.addr}/namespace/ns/blobs/{d}"
    cut = -(-len(blob) // patches)
    async with ClientSession() as http:
        async with http.post(f"{base}/uploads") as r:
            assert r.status == 200
            uid = await r.text()
        for off in range(0, len(blob), cut):
            async with http.patch(
                f"{base}/uploads/{uid}", data=blob[off:off + cut],
                headers={"X-Upload-Offset": str(off)},
            ) as r:
                assert r.status == 204
        async with http.put(f"{base}/uploads/{uid}/commit") as r:
            assert r.status == 201
        async with http.get(f"{base}/metainfo") as r:
            assert r.status == 200
        await asyncio.gather(*node.server._dedup_tasks)


@pytest.mark.parametrize("patches", [1, 2])
def test_every_step_of_a_push_is_counted_once(tmp_path, monkeypatch, patches):
    """(b) and (c): each step of the acknowledged path once a push
    (``patch.*`` once a PATCH), and the steps' cpu inside the process's
    own over the same stretch."""
    blob = os.urandom(3 * PIECE + 1000)
    seen: dict = {}
    # One push is too few stretches to estimate from: clock every one,
    # on books that hold nothing estimated by an earlier test's node.
    monkeypatch.setattr(PUSH_STEPS, "_every", 1)
    monkeypatch.setattr(PUSH_STEPS, "_rows", {})

    async def main():
        node = OriginNode(
            store_root=str(tmp_path / "o"), hasher="cpu",
            piece_lengths=PieceLengthConfig(table=((0, PIECE),)),
            backends=BackendManager([{
                "namespace": ".*", "backend": "file",
                "config": {"root": str(tmp_path / "remote")},
            }]),
        )
        await node.start()
        try:
            REGISTRY.render()  # runs collect_process
            seen["bill0"] = REGISTRY.counter("process_cpu_seconds_total").total()
            seen["entries0"], seen["cpu0"] = _entries(), _cpu()
            await _push(node, blob, patches)
            seen["entries1"], seen["cpu1"] = _entries(), _cpu()
            text = REGISTRY.render()
            seen["bill1"] = REGISTRY.counter("process_cpu_seconds_total").total()
            seen["text"] = text
        finally:
            await node.stop()

    asyncio.run(main())
    grown = {
        step: n - seen["entries0"].get(step, 0)
        for step, n in seen["entries1"].items()
    }
    assert {s: grown.get(s, 0) for s in ONCE_A_PUSH} == dict.fromkeys(ONCE_A_PUSH, 1)
    assert {s: grown.get(s, 0) for s in ONCE_A_PATCH} == dict.fromkeys(
        ONCE_A_PATCH, patches)
    # A PATCH past offset 0 of a journaled session reads the journal and
    # the spool's size first: two hops.
    assert grown.get("patch.guard", 0) == 2 * (patches - 1)
    assert grown.get("dedup.pass") == 1
    # (c) exclusive books: the steps' cpu is inside the process's bill.
    steps_cpu = seen["cpu1"] - seen["cpu0"]
    assert 0 < steps_cpu <= seen["bill1"] - seen["bill0"] + 0.005
    # The reader agrees: one push, and a bill for it.
    table = push_step_table("", seen["text"])
    assert table["pushes"] >= 1 and table["cpu_s"]["user"] > 0
    assert any(r["step"] == "commit.pin" for r in table["steps"])


def test_thread_classes_sum_to_the_process():
    reg = Registry()
    asyncio.run(asyncio.to_thread(sum, range(10 ** 6)))  # a worker has lived
    collect_process(reg)
    tick = 1.0 / os.sysconf("SC_CLK_TCK")
    for mode in ("user", "system"):
        total = _sample(reg, "process_cpu_seconds_total", mode=mode)
        classes = [
            _sample(reg, "process_thread_cpu_seconds_total", mode=mode,
                    **{"class": c})
            for c in ("loop", "worker", "ingest", "other")
        ]
        assert None not in classes and total is not None
        assert abs(sum(classes) - total) <= tick * (threading.active_count() + 1)
    assert _sample(reg, "process_context_switches_total", kind="voluntary") > 0
    assert _sample(reg, "process_context_switches_total", kind="involuntary") >= 0


@pytest.mark.parametrize("how", ["absent", "unreadable"])
def test_no_proc_no_thread_family(tmp_path, monkeypatch, how):
    """(d) Absent, not zero: the family renders no sample and the scrape
    raises nothing where /proc cannot be read."""
    reg = Registry()
    reg.add_scrape_hook(lambda: collect_process(reg, proc=str(tmp_path / "proc")))
    if how == "unreadable":
        os.makedirs(tmp_path / "proc" / "task")
        real_open = builtins.open

        def guarded(path, *args, **kwargs):
            if str(path).startswith(str(tmp_path / "proc")):
                raise PermissionError(13, "Permission denied", str(path))
            return real_open(path, *args, **kwargs)

        monkeypatch.setattr(builtins, "open", guarded)
    text = reg.render()
    assert "process_thread_cpu_seconds_total{" not in text
    assert 'process_cpu_seconds_total{mode="user"}' in text  # getrusage needs no /proc
