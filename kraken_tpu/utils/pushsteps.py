"""The push-step ledger: what each step of the upload API costs the
interpreter, in the two currencies its lock is paid in.

A push is served by one event loop and a dozen worker threads that take
turns on one interpreter lock. What a step costs that lock is its on-CPU
seconds plus the times it gives the lock up, and a step's wall is mostly
queueing for it (PERF.md section 5). Every step of the upload API runs
inside one of these wrappers, all of them round stretches that are where
they were (no call moves, no thread hop is added):

``push_step(name)``
    A stretch on ONE thread with no ``await`` inside: counted, and where
    it is clocked (see ``StepLedger``) ``time.perf_counter`` and
    ``time.thread_time`` are read on entry and exit.
``push_call(name, fn, *args)``
    The same round one call: what ``asyncio.to_thread`` is handed, so the
    step is clocked on the worker that runs it.
``push_await(name, awaitable)``
    The awaiting side of such a hop, wall only, as ``<name>.await``:
    hop and queueing = await - the worker's wall.
``push_loop(name, coro)``
    Awaited in ``coro``'s place: the coroutine is driven slice by slice
    (one ``send`` each) and only its slices on the thread are clocked:
    what it costs the loop, whatever it awaits.
``stepped(name)``
    A decorator: ``push_step`` round a plain function, ``push_loop`` round
    a coroutine function.

The books are EXCLUSIVE: a step opened inside another on the same thread
is taken off the outer one, so the steps' cpu sums to at most the
process's and ``<handler>.rest`` (utils/metrics.py's middleware; the
handler says its name with ``handler_step``) is what a request costs the
loop outside its named steps. A step that raises is booked too
(``timed_stage`` bills only what succeeds: its histogram is a latency,
this is a bill, and the lock was held either way).

Counters, per process: ``origin_push_steps_total{step}`` and
``origin_push_step_seconds_total{step,class,clock}`` (``clock="wall"|
"cpu"``; ``class`` the booking thread's, as in
``process_thread_cpu_seconds_total``). Read them with the process's own
bill (utils/metrics.py ``collect_process``) over
two scrapes: ``python -m kraken_tpu.utils.pushsteps before after``.
"""

from __future__ import annotations

import asyncio
import contextvars
import functools
import inspect
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from kraken_tpu.utils.metrics import REGISTRY, thread_class


# ``tls.top`` inside a stretch that is not clocked.
_OFF = object()


class StepLedger:
    """One per process (``PUSH_STEPS``); tests build their own over fake
    clocks and a registry of their own, with ``every=1``.

    **Entries are counted always; clocks are read for one stretch in
    ``every``.** A stretch is an outermost frame on its thread (a slice
    of a request's task, a pool call) with everything opened inside it;
    whether it is clocked is drawn when it opens, so every frame of a
    step is clocked with the same chance and the step's seconds are its
    clocked frames' times ``every``. A frame of a stretch that is not
    clocked costs a count in its thread's own dict and nothing else. The
    thread CPU clock is a system call, under gVisor (the machine that
    holds the chip) one of 6 us, and a frame in a live process costs
    several times what it does in a loop: with both clocks read round
    each of a push's 110 frames the origin lost a sixth of its pace
    (PERF.md section 6, PR 32)."""

    def __init__(self, wall=time.perf_counter, cpu=time.thread_time,
                 registry=REGISTRY, every: int = 1):
        self._wall = wall
        self._cpu = cpu
        self._every = every
        # .top: the innermost open frame of a clocked stretch, _OFF in
        # one that is not, None between stretches; .counts: this
        # thread's entries by step.
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._counts: list[dict[str, int]] = []  # every thread's .counts
        # (step, thread class) -> [wall, cpu or None] of clocked frames
        self._rows: dict[tuple[str, str], list] = {}
        self._offs: dict[tuple[str, bool], _Off] = {}
        self._seconds = registry.counter(
            "origin_push_step_seconds_total",
            "Seconds spent in each step of the upload API, exclusive of"
            " the steps inside it (clock=wall|cpu: perf_counter and the"
            " thread's own CPU clock; wall - cpu is time queued for the"
            " interpreter lock, the disk or a thread hop); estimated from"
            " the one stretch in a few that is clocked",
        )
        self._entries = registry.counter(
            "origin_push_steps_total",
            "Entries into each step of the upload API (step=create: one"
            " a push), every one counted",
        )
        registry.add_scrape_hook(self._mirror)

    def clocked(self) -> bool:
        """Drawn once a stretch: are its frames clocked?"""
        return self._every == 1 or random.random() * self._every < 1.0

    def count(self, step: str) -> None:
        try:
            counts = self._tls.counts
        except AttributeError:
            counts = self._tls.counts = {}
            with self._lock:
                self._counts.append(counts)
        counts[step] = counts.get(step, 0) + 1

    def book(self, step: str, wall: float, cpu: float | None) -> None:
        """A clocked frame of ``step`` has closed."""
        key = (step, thread_class(threading.current_thread().name))
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                self._rows[key] = [wall, cpu]
                return
            row[0] += wall
            if cpu is not None:
                row[1] += cpu

    def totals(self) -> tuple[dict[str, int], dict[tuple[str, str], tuple]]:
        """Entries by step, and (step, thread class) -> (wall seconds, cpu
        seconds or None) scaled up from the clocked frames."""
        with self._lock:
            counts = [dict(c) for c in self._counts]  # its thread may add
            rows = {key: tuple(row) for key, row in self._rows.items()}
        entries: dict[str, int] = {}
        for c in counts:
            for step, n in c.items():
                entries[step] = entries.get(step, 0) + n
        k = self._every
        return entries, {
            key: (wall * k, None if cpu is None else cpu * k)
            for key, (wall, cpu) in rows.items()
        }

    def _mirror(self) -> None:
        entries, seconds = self.totals()
        for step, n in entries.items():
            self._entries.set(n, step=step)
        for (step, cls), (wall, cpu) in seconds.items():
            labels = {"step": step, "class": cls}
            self._seconds.set(wall, clock="wall", **labels)
            if cpu is not None:
                self._seconds.set(cpu, clock="cpu", **labels)

    def step(self, name: str):
        top = getattr(self._tls, "top", None)
        if top is None:
            if self.clocked():
                return _Step(self, name)
        elif top is not _OFF:
            return _Step(self, name)
        key = (name, top is None)
        off = self._offs.get(key)
        if off is None:
            off = self._offs[key] = _Off(self, *key)
        return off

    def call(self, name: str, fn, *args, **kwargs):
        with self.step(name):
            return fn(*args, **kwargs)

    async def awaited(self, name: str, awaitable):
        t0 = self._wall() if self.clocked() else None
        try:
            return await awaitable
        finally:
            name += ".await"
            if t0 is not None:
                self.book(name, self._wall() - t0, None)
            self.count(name)

    def on_loop(self, name: str, coro) -> "_LoopSlices":
        return _LoopSlices(self, name, coro)

    def stepped(self, name: str):
        def wrap(fn):
            if inspect.iscoroutinefunction(fn):
                @functools.wraps(fn)
                async def stepped_coro(*args, **kwargs):
                    return await _LoopSlices(self, name, fn(*args, **kwargs))
                return stepped_coro

            @functools.wraps(fn)
            def stepped_call(*args, **kwargs):
                with self.step(name):
                    return fn(*args, **kwargs)
            stepped_call.push_step = name
            return stepped_call
        return wrap


class _Off:
    """A step's frame in a stretch that is not clocked, one object a
    step: it counts the entry. The outermost one marks the stretch."""

    __slots__ = ("_ledger", "_name", "_outermost")

    def __init__(self, ledger: StepLedger, name: str, outermost: bool):
        self._ledger = ledger
        self._name = name
        self._outermost = outermost

    def __enter__(self) -> "_Off":
        if self._outermost:
            self._ledger._tls.top = _OFF
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._outermost:
            self._ledger._tls.top = None
        self._ledger.count(self._name)
        return False


class _Frame:
    """An open frame of a clocked stretch: its clocks at entry and what
    the frames opened inside it have taken so far."""

    __slots__ = ("_ledger", "_name", "_parent", "_w0", "_c0", "_in_w", "_in_c")

    def __init__(self, ledger: StepLedger, name: str):
        self._ledger = ledger
        self._name = name

    def _open(self) -> None:
        ledger = self._ledger
        tls = ledger._tls
        self._parent = getattr(tls, "top", None)
        tls.top = self
        self._in_w = self._in_c = 0.0
        self._c0 = ledger._cpu()
        self._w0 = ledger._wall()

    def _close(self) -> None:
        """Book this frame's (wall, cpu) less the frames inside it."""
        ledger = self._ledger
        wall = ledger._wall() - self._w0
        cpu = ledger._cpu() - self._c0
        ledger._tls.top = parent = self._parent
        if parent is not None:
            parent._in_w += wall
            parent._in_c += cpu
        ledger.book(self._name, wall - self._in_w, cpu - self._in_c)


class _Step(_Frame):
    __slots__ = ()

    def __enter__(self) -> "_Step":
        self._open()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._close()
        self._ledger.count(self._name)
        return False


class _LoopSlices(_Frame):
    """Awaited in ``coro``'s place: every ``send`` (one slice on the
    thread) is a frame, a stretch of its own unless it runs inside one,
    and the coroutine counts as one entry when it ends. What it yields
    (the futures it waits on) passes through untouched, and what is
    thrown in (a cancellation) reaches it."""

    __slots__ = ("_coro",)

    def __init__(self, ledger: StepLedger, name: str, coro):
        super().__init__(ledger, name)
        self._coro = coro

    def _slice(self, resume, *args):
        ledger = self._ledger
        tls = ledger._tls
        top = getattr(tls, "top", None)
        clocked = ledger.clocked() if top is None else top is not _OFF
        if clocked:
            self._open()
        elif top is None:
            tls.top = _OFF
        ended = True  # returned (StopIteration) or raised
        try:
            out = resume(*args)
            ended = False
            return out
        finally:
            if clocked:
                self._close()
            elif top is None:
                tls.top = None
            if ended:
                ledger.count(self._name)

    def send(self, value):
        return self._slice(self._coro.send, value)

    def throw(self, *exc):
        return self._slice(self._coro.throw, *exc)

    def close(self):
        self._coro.close()

    def __await__(self):
        return self

    __iter__ = __await__

    def __next__(self):
        return self._slice(self._coro.send, None)


def install(loop: asyncio.AbstractEventLoop,
            ledger: StepLedger | None = None) -> None:
    """Name what ``loop``'s ``to_thread`` pool runs outside the upload
    API's own steps: every call handed to the default executor that is
    not a step already is one, ``thread.<function>``. The pool is the one
    asyncio would build itself (same size, same thread names); only what
    is submitted to it is wrapped. A node's process does this once,
    before it starts (cli.py), with :func:`name_http_server`."""
    ledger = ledger or PUSH_STEPS

    class StepPool(ThreadPoolExecutor):
        def submit(self, fn, /, *args, **kwargs):
            what = fn
            while isinstance(what, functools.partial):
                # to_thread hands over partial(Context.run, fn, *args).
                to_thread = isinstance(
                    getattr(what.func, "__self__", None), contextvars.Context
                )
                what = what.args[0] if to_thread and what.args else what.func
            if (getattr(what, "__func__", None) is StepLedger.call
                    or hasattr(what, "push_step")):
                # A step already (push_call, stepped): a frame round it
                # would book only what the frame itself costs.
                return super().submit(fn, *args, **kwargs)
            name = getattr(what, "__qualname__", type(what).__name__)
            return super().submit(
                ledger.call, "thread." + name, fn, *args, **kwargs
            )

    loop.set_default_executor(StepPool(thread_name_prefix="asyncio"))


def name_http_server() -> None:
    """What the loop runs for a request outside its handler is aiohttp's
    server: its entry points are wrapped where they are defined, once a
    process: ``http.connection`` (a connection's task: its keep-alive
    loop), ``http.request`` (a request's task: routing, the middlewares'
    glue), ``http.respond`` (``finish_response``: headers and body to
    the socket), ``http.access_log`` and ``http.parse``
    (``data_received``). A handler's steps come off its request's."""
    from aiohttp.web_protocol import RequestHandler

    for method, step in (("start", "http.connection"),
                         ("_handle_request", "http.request"),
                         ("finish_response", "http.respond"),
                         ("log_access", "http.access_log"),
                         ("data_received", "http.parse")):
        fn = getattr(RequestHandler, method)
        if not hasattr(fn, "__wrapped__"):
            setattr(RequestHandler, method, stepped(step)(fn))


def handler_step(name: str):
    """Name an aiohttp handler's step: the middleware of utils/metrics.py
    books what the request costs the loop outside the steps the handler
    names itself as ``<name>.rest`` (``http.rest`` for the others)."""
    def mark(fn):
        fn.push_step = name
        return fn
    return mark


PUSH_STEPS = StepLedger(every=32)
push_step = PUSH_STEPS.step
push_call = PUSH_STEPS.call
push_await = PUSH_STEPS.awaited
push_loop = PUSH_STEPS.on_loop
stepped = PUSH_STEPS.stepped



# -- reading the ledger over two scrapes -------------------------------------


def _samples(text: str) -> dict[tuple[str, tuple], float]:
    """(family, sorted label pairs) -> value of one ``/metrics`` text."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = tuple(sorted(
            (pair.partition("=")[0], pair.partition("=")[2].strip('"'))
            for pair in rest.rstrip("}").split(",") if pair
        ))
        try:
            out[name, labels] = float(value)
        except ValueError:
            continue
    return out


def push_step_table(before: str, after: str) -> dict:
    """What two scrapes of one origin say a push cost its interpreter.
    A push is one entry into ``create``. Seconds are a push's; ``cpu`` is
    user + system of the thread inside the step; ``coverage`` is the
    steps' cpu over the thread class's own (user + system), class by
    class: what share of the bill the named steps account for."""
    b, a = _samples(before), _samples(after)

    def grown(family: str) -> dict[tuple, float]:
        return {
            labels: value - b.get((name, labels), 0.0)
            for (name, labels), value in a.items() if name == family
        }

    entries = {
        dict(k)["step"]: v
        for k, v in grown("origin_push_steps_total").items()
    }
    pushes = entries.get("create", 0.0)
    if not pushes:
        return {"pushes": 0}
    seconds: dict[tuple[str, str], dict[str, float]] = {}
    for k, v in grown("origin_push_step_seconds_total").items():
        k = dict(k)
        seconds.setdefault((k["step"], k["class"]), {})[k["clock"]] = v
    steps = []
    for (step, cls), clocks in seconds.items():
        wall, cpu = clocks.get("wall", 0.0), clocks.get("cpu")
        n = entries.get(step, 0.0)
        if not n and not wall:
            continue  # nothing of it between the scrapes
        # A step's entries are its own whatever thread ran them: one that
        # ran on two classes of thread shows them on both rows.
        steps.append({
            "step": step, "class": cls, "entries_a_push": n / pushes,
            "wall_s": wall / pushes,
            "cpu_s": None if cpu is None else cpu / pushes,
            "waiting_s": None if cpu is None else (wall - cpu) / pushes,
        })
    steps.sort(key=lambda r: -(r["cpu_s"] or 0.0))
    process = {
        dict(k)["mode"]: v / pushes
        for k, v in grown("process_cpu_seconds_total").items()
    }
    switches = {
        dict(k)["kind"]: v / pushes
        for k, v in grown("process_context_switches_total").items()
    }
    classes: dict[str, dict] = {}
    for k, v in grown("process_thread_cpu_seconds_total").items():
        k = dict(k)
        classes.setdefault(k["class"], {})[k["mode"]] = v / pushes
    for cls, row in classes.items():
        row["steps_cpu"] = sum(
            r["cpu_s"] or 0.0 for r in steps if r["class"] == cls
        )
        bill = row.get("user", 0.0) + row.get("system", 0.0)
        row["coverage"] = row["steps_cpu"] / bill if bill else None
    return {"pushes": pushes, "cpu_s": process, "switches": switches,
            "classes": classes, "steps": steps}


def render_table(table: dict) -> str:
    if not table["pushes"]:
        return "no push between the two scrapes (create did not grow)\n"
    ms = lambda s: "      -" if s is None else f"{s * 1e3:7.3f}"  # noqa: E731
    out = [
        f"pushes {table['pushes']:.0f}; a push: cpu user "
        f"{ms(table['cpu_s'].get('user'))} ms, system "
        f"{ms(table['cpu_s'].get('system'))} ms; switches voluntary "
        f"{table['switches'].get('voluntary', 0.0):.1f}, involuntary "
        f"{table['switches'].get('involuntary', 0.0):.1f}",
        "class     user ms   sys ms  steps ms  coverage",
    ]
    for cls, row in sorted(table["classes"].items()):
        cover = "      -" if row["coverage"] is None else f"{row['coverage']:7.1%}"
        out.append(f"{cls:8} {ms(row.get('user'))}  {ms(row.get('system'))}"
                   f"   {ms(row['steps_cpu'])}   {cover}")
    out.append("step                     class   a push   cpu ms  wall ms"
               "  wait ms  cpu us/entry")
    for r in table["steps"]:
        each = ("" if r["cpu_s"] is None or not r["entries_a_push"]
                else f"{r['cpu_s'] / r['entries_a_push'] * 1e6:10.1f}")
        out.append(
            f"{r['step']:24} {r['class']:6} {r['entries_a_push']:7.2f}  "
            f"{ms(r['cpu_s'])}  {ms(r['wall_s'])}  {ms(r['waiting_s'])}  {each}"
        )
    return "\n".join(out) + "\n"


def main(argv: list[str] | None = None) -> int:
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(
        description="The push-step table of one origin over two scrapes of"
                    " its /metrics (files, as `curl` wrote them).")
    ap.add_argument("before")
    ap.add_argument("after")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    with open(args.before) as f, open(args.after) as g:
        table = push_step_table(f.read(), g.read())
    sys.stdout.write(json.dumps(table) + "\n" if args.json else render_table(table))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
