#!/usr/bin/env python3
"""Launcher for the one component that holds the chip.

    python benchmark/serve.py <control port> <component> [cli arguments ...]

Starts a control thread, then calls ``kraken_tpu.cli.main`` with the
arguments ``python -m kraken_tpu.cli`` would get: same entry point, same
configuration, same process; nothing of the served path is wrapped. Only
this process can say what the contract asks about the device (a parent that
imported JAX to ask would take the chip from it), so the control thread
answers, one JSON object per line on a loopback socket:

    {"op": "describe"}          platform, kind, count, peak_bytes_in_use,
                                compilations so far
    {"op": "start_trace", "dir": ..., "options": {...}}
                                python tracer off, host tracer 1, then the
                                ProfileOptions attributes the parent gives
    {"op": "stop_trace"}        returns when the xplane is written
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_COMPILES = {"n": 0, "seconds": 0.0}


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["n"] += 1
        _COMPILES["seconds"] += seconds


def _describe(jax) -> dict:
    devices = jax.devices()
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
        "compiles": _COMPILES["n"],
        "compile_s": _COMPILES["seconds"],
    }


def _answer(jax, req: dict) -> dict:
    op = req.get("op")
    if op == "describe":
        return _describe(jax)
    if op == "start_trace":
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        for key, value in req.get("options", {}).items():
            setattr(options, key, value)
        jax.profiler.start_trace(req["dir"], profiler_options=options)
        with jax.profiler.TraceAnnotation("bench_trace_open"):
            t = time.monotonic()
        return {"t_open": t}
    if op == "stop_trace":
        with jax.profiler.TraceAnnotation("bench_trace_close"):
            t = time.monotonic()
        jax.profiler.stop_trace()
        return {"t_close": t, "t_written": time.monotonic()}
    return {"error": f"unknown op {op!r}"}


def _control(jax, server: socket.socket) -> None:
    while True:
        conn, _ = server.accept()
        with conn, conn.makefile("rw") as f:
            for line in f:
                try:
                    out = _answer(jax, json.loads(line))
                except Exception as e:  # the parent decides what a failure means
                    out = {"error": f"{type(e).__name__}: {e}"}
                f.write(json.dumps(out) + "\n")
                f.flush()


def main() -> None:
    port, argv = int(sys.argv[1]), sys.argv[2:]
    import jax
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.devices()  # take the device now, on the main thread
    server = socket.create_server(("127.0.0.1", port))
    threading.Thread(target=_control, args=(jax, server), daemon=True).start()

    from kraken_tpu import cli

    cli.main(argv)


if __name__ == "__main__":
    main()
