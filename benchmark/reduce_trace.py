#!/usr/bin/env python3
"""From a profiler trace to the device's numbers. Runs on the CPU
(``JAX_PLATFORMS=cpu``), after the servers have exited:

    python benchmark/reduce_trace.py <xplane.pb> <out.json>

``busy_s`` is the union of the intervals of ONE line of each device plane
(``XLA Ops``; the planes' other lines cover the same intervals again),
averaged over the device planes. ``window_s`` is the longer of two lengths,
each read on one clock of its own: the stretch between ``serve.py``'s
``bench_trace_open`` and ``bench_trace_close`` annotations (host plane), and
the extent of the device plane's events. So 0 <= busy_s <= window_s by
construction, and nothing rests on the host's and the device's timestamps
agreeing: in one trace of PR 24 they lay more than 200 ms apart, and clipping
the device's events to the annotations left none. The tracer writes no device
plane for a stretch in which nothing ran: such a trace reads busy_s 0 over the
annotations' stretch, and the caller fails the run.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

BUSY_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
OPEN, CLOSE = "bench_trace_open", "bench_trace_close"


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def kernel_name(module: str) -> str:
    """``jit_sha256_tiles(1755...)`` -> ``jit_sha256_tiles``."""
    return re.sub(r"\(\d+\)$", "", module)


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lo, hi = float("inf"), float("-inf")
    t_open = t_close = None
    device = {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        if is_device:
            device[plane.name] = {}  # a plane with no event is an idle device
        lines = {}
        for line in plane.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
            if not events:
                continue
            lo = min(lo, min(e[0] for e in events))
            hi = max(hi, max(e[1] for e in events))
            if is_device:
                lines[line.name] = events
            else:
                for a, b, name in events:
                    if name == OPEN:
                        t_open = b
                    elif name == CLOSE:
                        t_close = a
        if is_device and lines:
            device[plane.name] = lines
    out = {"file_bytes": os.path.getsize(path), "device_planes": sorted(device)}
    stretch_ns = None
    if t_open is not None and t_close is not None and t_close > t_open:
        stretch_ns = t_close - t_open
    if stretch_ns is None and not any(device.values()):
        out["error"] = "the trace holds neither annotations nor device events"
        return out
    out["window_from"] = "annotations" if stretch_ns else "device events"
    out["t_open_ns"] = t_open if stretch_ns else lo

    busy_ns = 0.0
    extent_ns = 0.0
    modules: collections.Counter = collections.Counter()
    gaps: list[tuple[float, float]] = []
    used = BUSY_LINE
    for lines in device.values():
        events = lines.get(BUSY_LINE)
        if not events:
            events, used = lines.get(MODULE_LINE, []), MODULE_LINE
        merged = union([(a, b) for a, b, _ in events])
        busy_ns += sum(b - a for a, b in merged)
        if merged:
            extent_ns = max(extent_ns, merged[-1][1] - merged[0][0])
        gaps.extend((merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1))
        for a, b, name in lines.get(MODULE_LINE, []):
            modules[kernel_name(name)] += b - a
    n = max(1, len(device))
    window_ns = max(stretch_ns or 0.0, extent_ns)
    edge_ns = window_ns - extent_ns  # idle before the first and after the last event
    if edge_ns > 0:
        start = out["t_open_ns"]
        gaps.append((start, start + edge_ns))
    out.update(
        busy_line=used,
        busy_s=busy_ns / n / 1e9,
        window_s=window_ns / 1e9,
        device_ops=[[name, ns / n / 1e9] for name, ns in modules.most_common(10)],
        gaps_ns=sorted(gaps, key=lambda g: g[0] - g[1])[:10],
        n_events={name: {k: len(v) for k, v in lines.items()}
                  for name, lines in device.items()},
    )
    return out


def main() -> int:
    result = reduce(sys.argv[1])
    with open(sys.argv[2], "w") as f:
        json.dump(result, f)
    return 1 if "error" in result else 0


if __name__ == "__main__":
    sys.exit(main())
