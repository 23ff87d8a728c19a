#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that kraken-tpu still starts on the chip.

Drives the served path through the entry points an operator calls
(``python -m kraken_tpu.cli tracker|origin|agent`` with the shipped
``config/*/base.yaml``; only store paths, ports and the backend root are
overridden) and holds every answer to an oracle this file computes itself
with hashlib and NumPy:

- phase A: origin ``--hasher tpu`` on the chip, CPU tracker and agent.
  Seeded blobs go in through the upload API (a sub-piece blob, a 4 MiB-tier
  blob, an 8 MiB-tier blob); every served metainfo equals the hashlib
  oracle bit for bit, every pull through the agent equals the pushed
  bytes, and the origin's own /metrics must show the device hasher covered
  the bytes with ``ingest_fallbacks_total`` at zero -- an upload that fell
  back to hashlib would otherwise look exactly the same. The origin is
  then started a second time to show the compile cache warm.
- phase B: the agent ``--hasher tpu`` on the chip verifies pulls of the
  same blobs from a CPU origin.
- phase C: one child runs the dedup kernels (device gear pass, chunk
  SHA-256, MinHash sketch) against the host chunker, ``chunk_reference``,
  hashlib and a NumPy sketch.

``--four-chips`` runs INSTEAD of those the one thing that exists only
across chips: an origin with ``--hasher tpu-sharded`` over the 4 and 8 MiB
tiers, which must report four devices and rows on each of them.

The chip belongs to one process at a time: this parent never imports jax,
the children that need the chip run one after another, and every other
child is pinned to the CPU. The device named on the last line is what the
serving child itself reported on its READY line. Without a TPU the run
fails: at the real sizes as soon as the first device child says where it
landed, with ``--tiny`` (the CPU rehearsal) after every phase has run.

Every line printed is one JSON object; the last one, only on success, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import hashlib
import json
import os
import queue
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from kraken_tpu.core.digest import Digest  # noqa: E402
from kraken_tpu.origin.client import BlobClient  # noqa: E402
from kraken_tpu.utils.httputil import HTTPClient  # noqa: E402

WORK = os.path.join(REPO, ".chip_smoke")            # stores; removed at exit
LOGS = os.path.join(REPO, "chiprun_out", "chip_smoke")  # child logs; kept
NS = "smoke"
MIB = 1 << 20
GIB = 1 << 30
# The driver allows 1200 s; stop on our own terms, with a reason, before it.
TIME_LIMIT_S = 1140.0
T0 = time.monotonic()

# (name, bytes). Multiples of the shipped 64 MiB ingest window plus an odd
# tail, so each tier dispatches one full-window shape and a ragged tail.
BLOBS_FULL = (
    ("subpiece", 1 * MIB - 3),
    ("tier4", 320 * MIB + 123_457),
    ("tier8", 2 * GIB + 64 * MIB + 54_321),
)
BLOBS_TINY = (
    ("subpiece", 100_003),
    ("tier4", 8 * MIB + 12_345),
    ("tier4b", 16 * MIB + 777),
)
# The 16 MiB tier starts at 8 GiB (the piece table is not configurable from
# YAML). It is pushed in phase A when the disk and the clock allow it.
TIER16 = ("tier16", 8 * GIB + 64 * MIB + 98_765)
TIER16_DISK_GIB = 40      # origin + backend + agent + the pulled copy
RESERVE_AFTER_A_S = 420.0  # what phases B and C and the restart need
# One full window and a ragged tail: the first push after an origin start,
# timed cold and then warm against the compile cache.
PROBE_FULL = 64 * MIB + 4_321
PROBE_TINY = 4 * MIB + 321
GEAR_FULL = 64 * MIB + 12_345
GEAR_TINY = 5 * MIB + 12_345


class SmokeError(Exception):
    pass


def emit(**doc) -> None:
    print(json.dumps(doc), flush=True)


def elapsed() -> float:
    return round(time.monotonic() - T0, 1)


def time_left() -> float:
    return TIME_LIMIT_S - (time.monotonic() - T0)


def expected_piece_length(size: int) -> int:
    """The shipped piece-length table (origin/metainfogen.py)."""
    if size >= 8 * GIB:
        return 16 * MIB
    if size >= 2 * GIB:
        return 8 * MIB
    return 4 * MIB


# -- workload bytes, all from --seed ---------------------------------------

_CHUNK = 16 * MIB


class SeededBlob:
    """``size`` bytes determined by (seed, index), generated 16 MiB at a
    time so an 8 GiB blob never has to sit in memory: chunk k is one seeded
    random 16 MiB block XORed with a per-chunk 64-bit key."""

    def __init__(self, seed: int, index: int, name: str, size: int):
        self.name = name
        self.size = size
        rng = np.random.default_rng([seed, index])
        self._base = rng.integers(
            0, 1 << 64, size=_CHUNK // 8, dtype=np.uint64
        )
        self.digest: Digest | None = None
        self.piece_length = expected_piece_length(size)
        self.piece_hashes = b""

    def chunk(self, k: int) -> bytes:
        key = np.uint64(((k + 1) * 0x9E3779B97F4A7C15) % (1 << 64))
        n = min(_CHUNK, self.size - k * _CHUNK)
        return (self._base ^ key).view(np.uint8)[:n].tobytes()

    def chunks(self):
        for k in range(-(-self.size // _CHUNK)):
            yield self.chunk(k)

    def compute_oracle(self) -> None:
        """hashlib over the same bytes: the blob digest and every piece
        hash at the piece length the shipped table gives this size."""
        whole = hashlib.sha256()
        pieces = []
        plen = self.piece_length  # divides the 16 MiB chunk
        for data in self.chunks():
            whole.update(data)
            view = memoryview(data)
            for off in range(0, len(data), plen):
                pieces.append(hashlib.sha256(view[off:off + plen]).digest())
        self.digest = Digest.from_hex(whole.hexdigest())
        self.piece_hashes = b"".join(pieces)

    def open_at(self, offset: int) -> "_BlobReader":
        return _BlobReader(self, offset)

    def same_as_file(self, path: str) -> bool:
        if os.path.getsize(path) != self.size:
            return False
        with open(path, "rb") as f:
            return all(f.read(len(data)) == data for data in self.chunks())


class _BlobReader:
    """The ``open_at(offset)`` reader BlobClient.upload_from_opener wants."""

    def __init__(self, blob: SeededBlob, offset: int):
        self._blob = blob
        self._pos = offset

    def read(self, n: int) -> bytes:
        out = []
        while n > 0 and self._pos < self._blob.size:
            k, within = divmod(self._pos, _CHUNK)
            part = self._blob.chunk(k)[within:within + n]
            out.append(part)
            self._pos += len(part)
            n -= len(part)
        return b"".join(out)

    def close(self) -> None:
        pass


# -- children --------------------------------------------------------------

_CHILDREN: list["Child"] = []


def _child_env(chip: bool) -> dict:
    """CPU children are pinned to the CPU. The child that gets the chip is
    given nothing by this script: it inherits the caller's environment
    less any virtual-device flag, so on the chip machine JAX takes the TPU,
    and under a caller's own JAX_PLATFORMS=cpu (the rehearsal) it says so
    on its READY line and the run fails the device check."""
    env = dict(os.environ, PYTHONPATH=REPO)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.pop("XLA_FLAGS", ""),
    ).strip()
    if flags:
        env["XLA_FLAGS"] = flags
    if not chip:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class Child:
    """One process of the herd. stdout is pumped for the READY line (and
    kept draining); stderr, the JSON log, goes to a file under LOGS."""

    def __init__(self, name: str, argv: list[str], *, chip: bool):
        self.name = name
        self.log_path = os.path.join(LOGS, name + ".log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], stdout=subprocess.PIPE,
            stderr=self._log, env=_child_env(chip), cwd=REPO,
        )
        _CHILDREN.append(self)
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for raw in self.proc.stdout:
            self._lines.put(raw.decode(errors="replace").rstrip("\n"))
        self._lines.put(None)

    def next_line(self, timeout: float) -> str:
        try:
            line = self._lines.get(timeout=max(0.1, timeout))
        except queue.Empty:
            raise SmokeError(
                f"{self.name}: nothing on stdout in {timeout:.0f}s; "
                f"log tail: {self.log_tail()}"
            ) from None
        if line is None:
            raise SmokeError(
                f"{self.name} exited rc={self.proc.wait()}; "
                f"log tail: {self.log_tail()}"
            )
        return line

    def wait_ready(self, timeout: float = 180.0) -> dict:
        deadline = time.monotonic() + min(timeout, time_left())
        while True:
            line = self.next_line(deadline - time.monotonic())
            if line.startswith("READY "):
                return json.loads(line[len("READY "):])

    def log_tail(self, nbytes: int = 1500) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.log_path) - nbytes))
            return f.read().decode(errors="replace")

    def log_records(self, logger: str) -> list[dict]:
        """The child's JSON log records from one logger."""
        self._log.flush()
        out = []
        with open(self.log_path, "rb") as f:
            for raw in f:
                if not raw.startswith(b"{"):
                    continue
                try:
                    doc = json.loads(raw)
                except ValueError:
                    continue
                if doc.get("logger") == logger:
                    out.append(doc)
        return out

    def stop(self) -> None:
        """SIGINT is the CLI's immediate stop; the process is gone (and
        the chip free) when this returns."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        if self in _CHILDREN:
            _CHILDREN.remove(self)


def stop_all() -> None:
    for child in list(_CHILDREN):
        child.stop()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cli(component: str, *args: str) -> list[str]:
    return ["-m", "kraken_tpu.cli", component, "--host", "127.0.0.1", *args]


def http_client() -> HTTPClient:
    # One request may be a multi-GiB commit (the post-commit device pass
    # runs inside it) or a whole pull; never retry, a retry hides a fault.
    return HTTPClient(timeout_seconds=900, retries=0)


class Herd:
    """tracker + origin + agent on fixed loopback ports with the shipped
    configs, and the parent's two clients: ``oc`` pushes to the origin,
    ``http`` pulls through the agent and reads /metrics."""

    def __init__(self, tag: str, origin_hasher: str, agent_hasher: str):
        self.tag = tag
        self.root = os.path.join(WORK, tag)
        os.makedirs(self.root)
        self.hashers = {"origin": origin_hasher, "agent": agent_hasher}
        self.ports = {
            k: free_port()
            for k in ("tracker", "origin", "origin_p2p", "agent", "agent_p2p")
        }
        # The shipped origin config with the one path no flag reaches.
        origin_cfg = os.path.join(self.root, "origin.yaml")
        with open(origin_cfg, "w") as f:
            f.write(
                f"extends: {REPO}/config/origin/base.yaml\n"
                "backends:\n"
                '  - namespace: ".*"\n'
                "    backend: file\n"
                f"    config: {{root: {self.root}/backend}}\n"
            )
        self.configs = {
            "origin": origin_cfg, "agent": f"{REPO}/config/agent/base.yaml",
        }
        self.children: dict[str, Child] = {}
        self.ready: dict[str, dict] = {}
        self.oc = BlobClient(self.addr("origin"), http_client())
        self.http = http_client()

    def addr(self, who: str) -> str:
        return f"127.0.0.1:{self.ports[who]}"

    def start_tracker(self) -> None:
        self.children["tracker"] = Child(
            f"{self.tag}-tracker",
            cli("tracker", "--config", f"{REPO}/config/tracker/base.yaml",
                "--port", str(self.ports["tracker"]),
                "--origins", self.addr("origin")),
            chip=False,
        )
        self.children["tracker"].wait_ready()

    def start(self, role: str, suffix: str = "") -> float:
        """Start the origin or the agent; seconds until its READY line."""
        t0 = time.monotonic()
        self.children[role] = Child(
            f"{self.tag}-{role}{suffix}",
            cli(role, "--config", self.configs[role],
                "--store", f"{self.root}/{role}-store",
                "--port", str(self.ports[role]),
                "--p2p-port", str(self.ports[role + "_p2p"]),
                "--tracker", self.addr("tracker"),
                "--hasher", self.hashers[role]),
            chip=self.hashers[role] != "cpu",
        )
        self.ready[role] = self.children[role].wait_ready()
        return time.monotonic() - t0

    async def metrics(self, who: str) -> str:
        return (
            await self.http.get(f"http://{self.addr(who)}/metrics")
        ).decode()

    async def stop(self) -> None:
        await self.oc.close()
        await self.http.close()
        for child in self.children.values():
            child.stop()
        shutil.rmtree(self.root, ignore_errors=True)


@contextlib.asynccontextmanager
async def herd_phase(phase: str, origin_hasher: str, agent_hasher: str):
    """One phase's herd: whatever happens inside, its processes are gone
    (and the chip free) and its stores removed on the way out."""
    t0 = time.monotonic()
    herd = Herd(phase, origin_hasher, agent_hasher)
    try:
        herd.start_tracker()
        yield herd
    finally:
        await herd.stop()
    emit(event="phase_done", phase=phase,
         wall_s=round(time.monotonic() - t0, 1), t=elapsed())


# -- what the parent asks the herd -------------------------------------------


def metric(text: str, name: str, **labels: str) -> float:
    """Sum of the samples of ``name`` whose labels include ``labels``."""
    total = 0.0
    for line in text.splitlines():
        if not (line.startswith(name + "{") or line.startswith(name + " ")):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def device_of(ready: dict, who: str) -> dict:
    info = ready.get("hasher_devices")
    if info is None:
        raise SmokeError(f"{who}: READY line names no hasher devices: {ready}")
    return {
        "platform": info["platform"],
        "kind": info["device_kind"],
        "count": info["count"],
    }


# What only the chip can satisfy. At the real sizes the first such miss
# ends the run; the tiny CPU rehearsal notes it, goes on through every
# phase, and fails at the end.
_NEEDS_CHIP: list[str] = []


def needs_chip(problem: str, tiny: bool) -> None:
    if not tiny:
        raise SmokeError(problem)
    emit(event="needs_chip", problem=problem, t=elapsed())
    _NEEDS_CHIP.append(problem)


def check_device(device: dict, who: str, tiny: bool, want_count: int) -> None:
    emit(event="device", who=who, device=device, t=elapsed())
    if device["platform"] != "tpu" or device["count"] != want_count:
        needs_chip(
            f"{who} placed its hasher on {device}, not on {want_count} TPU "
            "device(s)",
            tiny,
        )


async def push(oc: BlobClient, blob: SeededBlob) -> float:
    """Upload through the upload API, then hold the served metainfo to the
    hashlib oracle. Returns the push seconds (upload + commit)."""
    t0 = time.monotonic()
    await oc.upload_from_opener(NS, blob.digest, blob.open_at)
    push_s = time.monotonic() - t0
    mi = await oc.get_metainfo(NS, blob.digest)
    if mi.length != blob.size or mi.piece_length != blob.piece_length:
        raise SmokeError(
            f"{blob.name}: metainfo says length {mi.length} piece_length "
            f"{mi.piece_length}, expected {blob.size} / {blob.piece_length}"
        )
    if mi.piece_hashes != blob.piece_hashes:
        got = mi.piece_hashes
        bad = [
            i for i in range(mi.num_pieces)
            if got[32 * i:32 * i + 32] != blob.piece_hashes[32 * i:32 * i + 32]
        ]
        raise SmokeError(
            f"{blob.name}: {len(bad)} of {mi.num_pieces} piece hashes differ "
            f"from hashlib (first: piece {bad[0]})"
        )
    return push_s


async def pull(herd: Herd, blob: SeededBlob) -> float:
    dest = os.path.join(herd.root, f"pulled-{blob.name}")
    t0 = time.monotonic()
    await herd.http.get_to_file(
        f"http://{herd.addr('agent')}/namespace/{NS}/blobs/{blob.digest.hex}",
        dest,
    )
    pull_s = time.monotonic() - t0
    same = await asyncio.to_thread(blob.same_as_file, dest)
    os.unlink(dest)
    if not same:
        raise SmokeError(f"{blob.name}: pulled bytes differ from pushed bytes")
    return pull_s


async def wait_dedup(herd: Herd, n_blobs: int) -> dict:
    """The dedup pass runs after the commit answers; wait until it has
    indexed every blob so its device work is inside the metrics read."""
    deadline = time.monotonic() + min(300.0, time_left())
    while True:
        stats = json.loads(
            await herd.http.get(f"http://{herd.addr('origin')}/dedup/stats")
        )
        if stats["blobs"] >= n_blobs:
            return stats
        if time.monotonic() > deadline:
            raise SmokeError(f"dedup pass indexed {stats} of {n_blobs} blobs")
        await asyncio.sleep(0.5)


async def maybe_tier16(args, herd: Herd, blobs, rows) -> None:
    """Push and pull the >= 8 GiB blob if the machine has the disk and the
    run has the time, judged from what the 8 MiB tier just took; otherwise
    say under "reduced" what was left out and why."""
    if args.tiny:
        return
    disk = shutil.disk_usage(WORK).free / GIB
    last = rows[-1]
    per_gib = (last["push_s"] + last["pull_s"]) / (last["bytes"] / GIB)
    size = TIER16[1]
    # Oracle in the parent (two hashlib passes), push, pull, dedup pass.
    projected = 2.5 * per_gib * (size / GIB)
    if disk < TIER16_DISK_GIB or time_left() - projected < RESERVE_AFTER_A_S:
        emit(reduced=[{
            "what": "16 MiB piece tier (blobs >= 8 GiB) was not pushed",
            "why": f"{disk:.0f} GiB of disk free (needs {TIER16_DISK_GIB}),"
                   f" {time_left():.0f} s left, the blob is projected to"
                   f" take {projected:.0f} s at the 8 MiB tier's"
                   f" {per_gib:.1f} s/GiB; tests/test_chip_compile.py"
                   " compiles the tier's programs for the v5e instead",
        }])
        return
    blob = SeededBlob(args.seed, len(blobs), *TIER16)
    t0 = time.monotonic()
    await asyncio.to_thread(blob.compute_oracle)
    row = {"blob": blob.name, "bytes": blob.size,
           "piece_length": blob.piece_length,
           "oracle_s": round(time.monotonic() - t0, 1),
           "push_s": round(await push(herd.oc, blob), 2)}
    row["pull_s"] = round(await pull(herd, blob), 2)
    blobs.append(blob)
    rows.append(row)


def origin_device_checks(metrics: str, hasher: str, pushed: int) -> dict:
    """Fail unless the origin's own counters say the device hasher did the
    work: an ingest that degraded to hashlib serves the same metainfo."""
    on_device = metric(metrics, "hasher_bytes_total", hasher=hasher)
    fallbacks = metric(metrics, "ingest_fallbacks_total")
    dedup_failures = metric(metrics, "origin_dedup_failures_total")
    out = {
        "pushed_bytes": pushed,
        f"hasher_bytes_total_{hasher}": on_device,
        "hasher_bytes_total_cpu": metric(
            metrics, "hasher_bytes_total", hasher="cpu"
        ),
        "ingest_fallbacks_total": fallbacks,
        "origin_dedup_failures_total": dedup_failures,
        "ingest_windows_total": metric(
            metrics, "ingest_windows_total", hasher=hasher
        ),
    }
    if on_device < pushed:
        raise SmokeError(f"device hasher covered too few bytes: {out}")
    if fallbacks or dedup_failures:
        raise SmokeError(f"the origin fell back or its dedup pass failed: {out}")
    return out


def chunker_impl(origin: Child) -> str:
    recs = origin.log_records("kraken.native")
    if not recs:
        return "not loaded"
    return {"c": "C library", "numpy": "NumPy fallback"}[recs[-1]["impl"]]


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO, ".jax_cache"
    )


def cache_entries() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except FileNotFoundError:
        return 0


# -- phases ----------------------------------------------------------------


async def phase_a(args, blobs: list[SeededBlob], probes: list[SeededBlob]):
    """Origin on the chip."""
    async with herd_phase("a", "tpu", "cpu") as herd:
        entries0 = cache_entries()
        start_cold = herd.start("origin")
        device = device_of(herd.ready["origin"], "origin")
        check_device(device, "phase A origin", args.tiny, 1)
        herd.start("agent")

        cold_push = await push(herd.oc, probes[0])
        entries1 = cache_entries()
        rows = []
        for blob in blobs:
            rows.append({"blob": blob.name, "bytes": blob.size,
                         "piece_length": blob.piece_length,
                         "push_s": round(await push(herd.oc, blob), 2)})
        for blob, row in zip([probes[0], *blobs], [{}, *rows]):
            row["pull_s"] = round(await pull(herd, blob), 2)
        await maybe_tier16(args, herd, blobs, rows)
        stats = await wait_dedup(herd, len(blobs) + 1)
        pushed = probes[0].size + sum(b.size for b in blobs)
        checks = origin_device_checks(
            await herd.metrics("origin"), "tpu", pushed
        )
        emit(event="phase_a", blobs=rows, checks=checks,
             dedup_route=stats["chunk_route"],
             dedup_route_measured_bps=stats["chunk_route_measured"],
             host_chunker=chunker_impl(herd.children["origin"]), t=elapsed())

        # A second start of the same origin: the shapes it compiled are in
        # the persistent cache now, so its first push should not pay them.
        herd.children["origin"].stop()
        await herd.oc.close()  # drop the keep-alive that died with it
        start_warm = herd.start("origin", "-restart")
        warm_push = await push(herd.oc, probes[1])
        origin_device_checks(
            await herd.metrics("origin"), "tpu", probes[1].size
        )
        if cache_entries() == 0:
            # Seconds-long Mosaic compiles fill it; the CPU's sub-second
            # ones fall under JAX's own threshold for caching.
            needs_chip(f"compile cache {cache_dir()} stayed empty", args.tiny)
        emit(event="compile_cache", dir=cache_dir(),
             set_by="JAX_COMPILATION_CACHE_DIR"
             if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "the program",
             entries_before=entries0, entries_after_first_push=entries1,
             entries_now=cache_entries(),
             first_start_to_ready_s=round(start_cold, 2),
             first_start_first_push_s=round(cold_push, 2),
             second_start_to_ready_s=round(start_warm, 2),
             second_start_first_push_s=round(warm_push, 2),
             probe_bytes=probes[0].size, t=elapsed())
    return device


async def phase_b(args, blobs: list[SeededBlob]):
    """Agent on the chip: a CPU origin seeds, the agent verifies."""
    async with herd_phase("b", "cpu", "tpu") as herd:
        herd.start("origin")
        start_s = herd.start("agent")
        device = device_of(herd.ready["agent"], "agent")
        check_device(device, "phase B agent", args.tiny, 1)
        rows = []
        for blob in blobs:
            await push(herd.oc, blob)
            rows.append({"blob": blob.name, "bytes": blob.size,
                         "pull_s": round(await pull(herd, blob), 2)})
        metrics = await herd.metrics("agent")
        pulled = sum(b.size for b in blobs)
        checks = {
            "pulled_bytes": pulled,
            "hasher_bytes_total_tpu": metric(
                metrics, "hasher_bytes_total", hasher="tpu"),
            "verify_batches_total_tpu": metric(
                metrics, "verify_batches_total", path="tpu"),
            "verify_batches_total_host": metric(
                metrics, "verify_batches_total", path="host"),
            "verify_pieces_total": metric(metrics, "verify_pieces_total"),
        }
        if (checks["hasher_bytes_total_tpu"] < pulled
                or checks["verify_batches_total_tpu"] <= 0
                or checks["verify_batches_total_host"]):
            raise SmokeError(f"agent did not verify on the device: {checks}")
        emit(event="phase_b", blobs=rows, checks=checks,
             agent_start_to_ready_s=round(start_s, 2), t=elapsed())
    return device


def phase_c(args) -> dict:
    """The dedup kernels, in one child of their own: they run here
    whatever the origin's router decided in phase A."""
    t_phase = time.monotonic()
    child = Child(
        "c-dedup",
        [os.path.abspath(__file__), "--child-dedup", "--seed", str(args.seed),
         *(["--tiny"] if args.tiny else [])],
        chip=True,
    )
    try:
        doc = json.loads(child.next_line(min(600.0, time_left())))
        rc = child.proc.wait(timeout=60)
    finally:
        child.stop()
    emit(event="phase_c", **doc, t=elapsed())
    if rc != 0 or not doc.get("all_equal"):
        raise SmokeError(f"dedup kernels disagree with the host: {doc}")
    device = doc["device"]
    check_device(device, "phase C child", args.tiny, 1)
    emit(event="phase_done", phase="c", wall_s=round(
        time.monotonic() - t_phase, 1), t=elapsed())
    return device


async def phase_four_chips(args, blobs: list[SeededBlob]):
    """The sharded plane: ``--hasher tpu-sharded`` over the 4 and 8 MiB
    tiers, four devices each holding rows."""
    async with herd_phase("four", "tpu-sharded", "cpu") as herd:
        herd.start("origin")
        device = device_of(herd.ready["origin"], "origin")
        check_device(device, "sharded origin", args.tiny, 4)
        herd.start("agent")
        rows = []
        for blob in blobs:
            row = {"blob": blob.name, "bytes": blob.size,
                   "piece_length": blob.piece_length,
                   "push_s": round(await push(herd.oc, blob), 2)}
            row["pull_s"] = round(await pull(herd, blob), 2)
            rows.append(row)
        checks = origin_device_checks(
            await herd.metrics("origin"), "tpu-sharded",
            sum(b.size for b in blobs),
        )
        first = herd.children["origin"].log_records("kraken.hashplane")
        if not first:
            raise SmokeError("the sharded hasher logged no first dispatch")
        rows_per_device = first[0]["rows_per_device"]
        emit(event="four_chips", blobs=rows, checks=checks,
             rows_per_device=rows_per_device, t=elapsed())
        if len(rows_per_device) != 4 or not all(rows_per_device.values()):
            needs_chip(
                f"not four devices with rows each: {rows_per_device}",
                args.tiny,
            )
    return device


# -- the phase C child (the only code here that imports jax) -----------------


def child_dedup(args) -> int:
    import jax

    from kraken_tpu.core.hasher import get_hasher
    from kraken_tpu.ops.cdc import CDCParams, chunk, chunk_host, chunk_reference
    from kraken_tpu.ops.minhash import MinHasher, fingerprints_from_digests

    dev = jax.devices()[0]
    n = GEAR_TINY if args.tiny else GEAR_FULL
    rng = np.random.default_rng([args.seed, 1000])
    buf = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
    params = CDCParams()

    t0 = time.monotonic()
    device_cuts = [int(c) for c in chunk(buf, params)]  # gear pass on device
    gear_first_s = time.monotonic() - t0
    t0 = time.monotonic()
    chunk(buf, params)
    gear_second_s = time.monotonic() - t0
    host_cuts = [int(c) for c in chunk_host(buf, params)]
    # chunk_reference is a Python loop: hold it to a 2 MiB prefix. All of
    # its cuts but the last (forced by the prefix's end) are cuts of the
    # whole buffer too, because cuts are chosen left to right.
    ref_cuts = chunk_reference(buf[: 2 * MIB], params)[:-1]

    chunks = [
        memoryview(buf)[s:e]
        for s, e in zip([0, *device_cuts[:-1]], device_cuts)
    ]
    t0 = time.monotonic()
    device_digests = get_hasher("tpu").hash_batch(chunks)
    sha_s = time.monotonic() - t0
    host_digests = np.stack([
        np.frombuffer(hashlib.sha256(c).digest(), dtype=np.uint8)
        for c in chunks
    ])
    # An agent's verify batch: equal-length pieces, which on the chip take
    # the uniform tile kernel and not the ragged one the chunks above took.
    plen = 1 * MIB if args.tiny else 4 * MIB
    pieces = [memoryview(buf)[i * plen:(i + 1) * plen] for i in range(3)]
    t0 = time.monotonic()
    piece_digests = get_hasher("tpu").hash_batch(pieces)
    piece_sha_s = time.monotonic() - t0
    pieces_equal = all(
        bytes(row) == hashlib.sha256(p).digest()
        for row, p in zip(piece_digests, pieces)
    )
    hasher = MinHasher()
    device_sketch = hasher.sketch(fingerprints_from_digests(device_digests))
    fps = fingerprints_from_digests(host_digests)
    host_sketch = (
        fps[:, None] * hasher._a[None, :] + hasher._b[None, :]
    ).min(axis=0)  # uint32 arithmetic wraps mod 2^32, as on the device

    doc = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "gear_bytes": n,
        "cuts": len(device_cuts),
        "cuts_equal_host_chunker": device_cuts == host_cuts,
        "cuts_equal_chunk_reference_prefix":
            device_cuts[: len(ref_cuts)] == ref_cuts and len(ref_cuts) > 0,
        "chunk_sha_equal_hashlib": bool((device_digests == host_digests).all()),
        "piece_batch_sha_equal_hashlib": pieces_equal,
        "sketch_equal_host": bool((device_sketch == host_sketch).all()),
        "gear_first_s": round(gear_first_s, 2),
        "gear_second_s": round(gear_second_s, 2),
        "chunk_sha_s": round(sha_s, 2),
        "piece_batch_sha_s": round(piece_sha_s, 2),
    }
    doc["all_equal"] = all(
        v for k, v in doc.items() if k.endswith(("_host_chunker", "_prefix",
                                                 "_hashlib", "_host"))
    )
    print(json.dumps(doc), flush=True)
    return 0 if doc["all_equal"] else 1


# -- main --------------------------------------------------------------------


def make_blobs(seed: int, specs, first_index: int = 0) -> list[SeededBlob]:
    blobs = [
        SeededBlob(seed, first_index + i, name, size)
        for i, (name, size) in enumerate(specs)
    ]
    for blob in blobs:
        blob.compute_oracle()
    return blobs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every workload byte")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tpu-sharded origin (needs 4 chips)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal sizes; every phase runs, and "
                         "without a TPU the run still fails at the end")
    ap.add_argument("--child-dedup", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_dedup:
        return child_dedup(args)

    shutil.rmtree(WORK, ignore_errors=True)
    shutil.rmtree(LOGS, ignore_errors=True)
    os.makedirs(WORK)
    os.makedirs(LOGS)
    emit(event="start", seed=args.seed, tiny=args.tiny,
         four_chips=args.four_chips, host_cpus=os.cpu_count(),
         disk_free_gib=round(shutil.disk_usage(WORK).free / GIB, 1),
         compile_cache=cache_dir())

    specs = BLOBS_TINY if args.tiny else BLOBS_FULL
    t0 = time.monotonic()
    blobs = make_blobs(args.seed, specs)
    probe_size = PROBE_TINY if args.tiny else PROBE_FULL
    probes = make_blobs(
        args.seed, [("probe0", probe_size), ("probe1", probe_size)], 100
    )
    emit(event="oracle", blobs={b.name: b.size for b in blobs},
         seconds=round(time.monotonic() - t0, 1))
    try:
        if args.four_chips:
            devices = [asyncio.run(phase_four_chips(args, blobs[1:]))]
        else:
            devices = [
                asyncio.run(phase_a(args, list(blobs), probes)),
                asyncio.run(phase_b(args, blobs)),
                phase_c(args),
            ]
    except SmokeError as e:
        emit(event="failed", error=str(e), t=elapsed())
        return 1
    finally:
        stop_all()
        shutil.rmtree(WORK, ignore_errors=True)

    if "jax" in sys.modules:
        emit(event="failed", error="the parent imported jax")
        return 1
    if _NEEDS_CHIP or any(d != devices[0] for d in devices):
        emit(event="failed", t=elapsed(), devices=devices,
             error="every phase ran, but not on the chip: "
             + "; ".join(_NEEDS_CHIP))
        return 1
    emit(event="done", wall_s=elapsed())
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
