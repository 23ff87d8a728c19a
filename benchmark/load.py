"""Closed-loop load over the served path, from the client's side.

A push is ``POST .../uploads`` -> ``PATCH`` (16 MiB a request) ->
``PUT .../commit`` -> ``GET .../metainfo``; a pull is ``GET`` of the blob
through an agent. A rollout (``drive.op: "rollout"``) is one blob pulled
through every agent of the herd at once, as a deploy pulls one layer on
every host; it ends when every agent's pull has been compared, and the blob
is then deleted from every agent. Every answer is held to the reference as
it arrives: the served metainfo to hashlib over the generator's bytes, the
delivered bytes to the bytes generated. One record a blob and agent, with
the client's spans.
"""

from __future__ import annotations

import asyncio
import json
import os
import time

import aiohttp

from blobs import CHUNK, SeededBlob, piece_length_for

NS = "bench"
WARM_INDEX = 1 << 20  # warm-up blobs draw their bytes from indices of their own
_TIMEOUT = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=300)


def _now() -> float:
    return time.monotonic()


class Load:
    def __init__(self, herd, config: dict, mix: dict, seed: int, sizes: list[int]):
        self.herd = herd
        self.op = config["drive"]["op"]
        self.clients = mix.get("clients", config["drive"]["clients"])
        self.table = config["shipped"]["piece_lengths"]
        self.mix = mix
        self.seed = seed
        self.sizes = sizes             # one deal of the deck
        self.records: list[dict] = []  # finished operations, warm-up included
        self.bytes_moved = 0           # payload bytes sent or received so far
        self.phase_bytes = 0           # of those, between the last phase's open and close
        self.gen_s = 0.0               # client seconds spent producing bytes
        self.kept: list[SeededBlob] = []  # pushed blobs held for the read-back
        self.pool: list[SeededBlob] = []  # pull: the seeded blobs, in deal order
        self._busy: set[int] = set()
        self._next = 0
        self._limit: int | None = None  # pull: where this phase's deal ends
        self._closing = False
        self._end: asyncio.Event | None = None
        self._ready: asyncio.Queue | None = None
        self._janitor_q: asyncio.Queue | None = None
        self.http: aiohttp.ClientSession | None = None

    # -- blobs ---------------------------------------------------------------

    def make_blob(self, index: int, size: int) -> SeededBlob:
        blob = SeededBlob(self.seed, index, size, piece_length_for(size, self.table),
                          self.mix["bytes"])
        blob.compute_reference()
        return blob

    async def _producer(self, first_index: int, count: int | None) -> None:
        """Makes blobs and their reference ahead of the clients, off the
        request path; the queue's bound keeps it a few blobs ahead."""
        i = 0
        while count is None or i < count:
            size = self.sizes[i % len(self.sizes)]
            blob = await asyncio.to_thread(self.make_blob, first_index + i, size)
            await self._ready.put(blob)
            i += 1

    # -- one push --------------------------------------------------------------

    def _url(self, role: str, path: str) -> str:
        return f"http://{self.herd.addr(role)}{path}"

    async def push(self, blob: SeededBlob, role: str = "origin") -> dict:
        base = f"/namespace/{NS}/blobs/sha256:{blob.hex}"
        rec = {"op": "push", "index": blob.index, "bytes": blob.size,
               "pieces": blob.n_pieces, "t_start": _now(), "gen_s": 0.0,
               "ok": False, "why": "", "fault": ""}
        async with self.http.post(self._url(role, base + "/uploads")) as r:
            r.raise_for_status()
            uid = await r.text()
        for k in range(blob.n_chunks):
            t = _now()
            data = await asyncio.to_thread(blob.chunk, k)
            rec["gen_s"] += _now() - t
            async with self.http.patch(
                self._url(role, f"{base}/uploads/{uid}"), data=data,
                headers={"X-Upload-Offset": str(k * CHUNK)},
            ) as r:
                r.raise_for_status()
            self.bytes_moved += len(data)
        rec["t_patched"] = _now()
        async with self.http.put(self._url(role, f"{base}/uploads/{uid}/commit")) as r:
            r.raise_for_status()
        rec["t_committed"] = _now()
        async with self.http.get(self._url(role, base + "/metainfo")) as r:
            r.raise_for_status()
            served = await r.read()
        rec["t_end"] = _now()
        rec["why"] = metainfo_fault(served, blob)
        rec["ok"] = not rec["why"]
        rec["fault"] = "" if rec["ok"] else "wrong"
        return rec

    # -- one pull --------------------------------------------------------------

    async def pull(self, blob: SeededBlob, role: str = "agent") -> dict:
        rec = {"op": "pull", "agent": role, "index": blob.index, "bytes": blob.size,
               "pieces": blob.n_pieces, "t_start": _now(), "gen_s": 0.0,
               "ok": False, "why": "", "fault": ""}
        got = 0
        differs = False
        held = bytearray()  # delivered and counted, not yet compared
        reader = blob.stream()

        async def compare() -> None:
            nonlocal got, differs, held
            t = _now()
            if not differs:
                differs = await asyncio.to_thread(reader.differs_at, got, held)
            rec["gen_s"] += _now() - t
            got += len(held)
            held = bytearray()

        path = f"/namespace/{NS}/blobs/sha256:{blob.hex}"
        async with self.http.get(self._url(role, path)) as r:
            r.raise_for_status()
            # A response arrives in reads of some 64 KiB; a hop to a thread
            # for each cost a pull a fifth of its span (PERF.md section 6,
            # PR 35). Bytes are counted as they arrive, compared 4 MiB at a time.
            async for data in r.content.iter_chunked(4 << 20):
                held += data
                self.bytes_moved += len(data)
                if len(held) >= 4 << 20:
                    await compare()
            if held:
                await compare()
        rec["t_end"] = _now()
        if differs or got != blob.size:
            rec["why"] = f"delivered {got} of {blob.size} bytes, differs={differs}"
        rec["ok"] = not rec["why"]
        rec["fault"] = "" if rec["ok"] else "wrong"
        return rec

    async def _delete(self, role: str, blob: SeededBlob) -> None:
        path = f"/blobs/sha256:{blob.hex}"
        if role == "origin":
            path = f"/namespace/{NS}" + path
        async with self.http.delete(self._url(role, path)) as r:
            r.raise_for_status()

    # -- the loop ----------------------------------------------------------------

    async def _attempt(self, blob: SeededBlob, phase: str, role: str = "agent") -> dict:
        """One push, or one pull through ``role``, recorded."""
        try:
            rec = await (self.push(blob) if self.op == "push" else self.pull(blob, role))
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            rec = {"op": "push" if self.op == "push" else "pull", "index": blob.index,
                   "bytes": blob.size, "t_start": _now(), "t_end": _now(), "gen_s": 0.0,
                   "ok": False, "why": f"{type(e).__name__}: {e}", "fault": "unanswered"}
            if self.op != "push":
                rec["agent"] = role
        rec["phase"] = phase
        self.gen_s += rec["gen_s"]
        self.records.append(rec)
        return rec

    async def _one(self, blob: SeededBlob, phase: str) -> None:
        if self.op == "rollout":
            agents = self.herd.agents
            await asyncio.gather(*(self._attempt(blob, phase, role) for role in agents))
            await asyncio.gather(*(self._delete(role, blob) for role in agents))
            return
        rec = await self._attempt(blob, phase)
        if self.op == "pull":
            await self._delete("agent", blob)
        elif rec["ok"] and phase == "window" and self._keep(blob):
            self.kept.append(blob)
        else:
            self._janitor_q.put_nowait(blob)

    def _keep(self, blob: SeededBlob) -> bool:
        return blob.index % self.mix["readback_every"] == 0

    async def _client(self, phase: str) -> None:
        while not self._closing:
            if self.op == "push":
                t = _now()
                blob = await self._ready.get()
                self.gen_s += _now() - t
                if blob is None:
                    return
            else:
                blob = self._next_free()
                if blob is None:
                    return
            try:
                await self._one(blob, phase)
            finally:
                self._busy.discard(blob.index)

    def _next_free(self) -> SeededBlob | None:
        """The pool in deal order, round and round; a blob another client
        still has in flight is passed over."""
        if self._limit is not None and self._next >= self._limit:
            return None
        for _ in range(len(self.pool)):
            blob = self.pool[self._next % len(self.pool)]
            self._next += 1
            if blob.index not in self._busy:
                self._busy.add(blob.index)
                return blob
        return None

    async def _janitor(self) -> None:
        """Keeps disk use flat: a checked blob leaves the origin once its
        write-back to the file backend has landed (or after 20 s), and the
        backend's copy goes with it."""
        waiting: list[tuple[float, SeededBlob]] = []
        while True:
            while not self._janitor_q.empty():
                waiting.append((_now(), self._janitor_q.get_nowait()))
            still = []
            for since, blob in waiting:
                copy = os.path.join(self.herd.backend_root, blob.hex)
                if os.path.exists(copy) or _now() - since > 20:
                    try:
                        await self._delete("origin", blob)
                    except (aiohttp.ClientError, OSError):
                        pass
                    try:
                        os.unlink(copy)
                    except FileNotFoundError:
                        pass
                else:
                    still.append((since, blob))
            waiting = still
            await asyncio.sleep(0.25)

    async def run_phase(self, phase: str, seconds: float | None,
                        blobs: int | None, first_index: int,
                        on_open=None) -> tuple[float, float]:
        """Drive the clients for ``seconds`` (the window) or over ``blobs``
        blobs (warm-up); in-flight work is then drained and checked.
        Returns the phase's open and close times; ``phase_bytes`` is what
        crossed the wire between the two, read at the close itself."""
        self._closing = False
        self._end = asyncio.Event()
        self._limit = None if blobs is None else self._next + blobs
        producer = None
        if self.op == "push":
            self._ready = asyncio.Queue(maxsize=self.clients)
            producer = asyncio.create_task(self._producer(first_index, blobs))
            if blobs is None:
                # Start with a full queue, so the first pushes wait for nobody.
                while not self._ready.full():
                    await asyncio.sleep(0.01)
        t_open = _now()
        bytes_open = self.bytes_moved
        tasks = [asyncio.create_task(self._client(phase)) for _ in range(self.clients)]
        extra = asyncio.create_task(on_open(t_open)) if on_open else None
        if seconds is not None:
            try:  # the window's length, unless end_early() cuts it
                await asyncio.wait_for(
                    self._end.wait(), max(0.0, t_open + seconds - _now()))
            except asyncio.TimeoutError:
                pass
            t_close = _now()
            self.phase_bytes = self.bytes_moved - bytes_open
            self._closing = True
            if producer is not None:
                producer.cancel()
                for _ in tasks:  # wake clients that wait for a blob
                    try:
                        self._ready.put_nowait(None)
                    except asyncio.QueueFull:
                        break
        else:
            if producer is not None:
                await producer
                for _ in tasks:
                    await self._ready.put(None)
            t_close = None
        await asyncio.wait_for(asyncio.gather(*tasks), 150)
        if extra is not None:
            await extra
        return t_open, t_close if t_close is not None else _now()

    def end_early(self) -> None:
        self._end.set()

    async def open(self) -> None:
        self.http = aiohttp.ClientSession(
            timeout=_TIMEOUT,
            connector=aiohttp.TCPConnector(limit=0, keepalive_timeout=60),
        )
        self._janitor_q = asyncio.Queue()
        self._janitor_task = asyncio.create_task(self._janitor())

    async def close(self) -> None:
        self._janitor_task.cancel()
        await self.http.close()

    async def seed_pool(self) -> None:
        """Pull and rollout cells: push this seed's deal through the (CPU)
        origin."""
        blobs = await asyncio.gather(*[
            asyncio.to_thread(self.make_blob, i, size)
            for i, size in enumerate(self.sizes)
        ])
        sem = asyncio.Semaphore(4)

        async def one(blob):
            async with sem:
                rec = await self.push(blob)
            if not rec["ok"]:
                raise RuntimeError(f"seeding blob {blob.index}: {rec['why']}")

        await asyncio.gather(*[one(b) for b in blobs])
        self.pool = list(blobs)

    async def read_back(self) -> list[dict]:
        """Push cells, after the window: the kept blobs come back through
        the agent and are compared byte for byte."""
        out = []
        for blob in self.kept:
            try:
                rec = await self.pull(blob)
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
                rec = {"index": blob.index, "bytes": blob.size, "ok": False,
                       "why": f"{type(e).__name__}: {e}"}
            out.append(rec)
        return out

    async def metrics_text(self, role: str) -> str:
        async with self.http.get(self._url(role, "/metrics")) as r:
            r.raise_for_status()
            return await r.text()


def metainfo_fault(served: bytes, blob: SeededBlob) -> str:
    """'' when the served metainfo is the reference's, else what differs."""
    try:
        doc = json.loads(served)
        info = doc["info"]
        got = bytes.fromhex(info["piece_hashes"])
    except (ValueError, KeyError, TypeError) as e:
        return f"metainfo unreadable: {e}"
    if doc.get("digest") != "sha256:" + blob.hex:
        return f"digest {doc.get('digest')}"
    if info.get("length") != blob.size or info.get("piece_length") != blob.piece_length:
        return (f"length {info.get('length')} piece_length "
                f"{info.get('piece_length')}, expected {blob.size} / {blob.piece_length}")
    if got != blob.piece_hashes:
        bad = [i for i in range(blob.n_pieces)
               if got[32 * i:32 * i + 32] != blob.piece_hashes[32 * i:32 * i + 32]]
        return f"{len(bad)} of {blob.n_pieces} piece hashes differ (first: piece {bad[:1]})"
    return ""
