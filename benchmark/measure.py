#!/usr/bin/env python3
"""Several runs of run.py in one call, for the builder's chip sessions:

    chiprun -- python3 benchmark/measure.py <tag> <cell>:<seed>:<trace>[:<seconds>[:control|fault=<name>]] ...

Each run is a process of its own, one after another (``control``: the chip's
component hashes on the host; ``fault=<name>``: the served path is broken by
``tests/faulty_serve.py``; both have to come out not correct). Result lines, the
earlier lines and, for a run that printed no result, the herd's logs go to
``chiprun_out/bench/<tag>/``; a summary of every run is printed at the end.
Also reduces sets of runs to the spread the contract's bounds are set from
(by quartiles, and as the driver's notes take it), and says where in a window
a tail lives: ``push_p90`` of the pushes begun in its first and in its second
half, from the run's own client records.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from readers import read_metric  # noqa: E402


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed_spread(values: list[float]) -> float:
    """Largest less smallest over the median, the run farthest from the
    median left out where that narrows it: what the driver's notes in
    PERF_LEDGER.jsonl call a spread."""
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))
    if len(kept) > 2:
        kept = kept[:-1]
    return (max(kept) - min(kept)) / med


def half_tails(records_path: str, metric: str = "push_p90") -> dict | None:
    """``metric`` as its own reader takes it over the whole window, taken
    over the operations the window began in its first half and over those
    it began in its second."""
    with open(records_path) as f:
        rows = [json.loads(line) for line in f]
    rows = [r for r in rows if r.get("phase") == "window"]
    if len(rows) < 20:
        return None
    t0 = min(r["t_start"] for r in rows)
    mid = t0 + (max(r["t_start"] for r in rows) - t0) / 2
    halves = [[r for r in rows if (r["t_start"] >= mid) == late] for late in (False, True)]
    first, second = (read_metric(metric, {"records": half}) for half in halves)
    return {"first_half": first, "second_half": second, "n": [len(h) for h in halves]}


def watch_pauses(into: list, stop: threading.Event, tick: float = 0.05) -> None:
    """This process's own late wake-ups of 0.3 s or more, on the clock the
    client records use (CLOCK_MONOTONIC is the machine's): a stop in every
    client's answers that is also here stopped the machine, not the origin."""
    last = time.monotonic()
    while not stop.wait(tick):
        now = time.monotonic()
        if now - last - tick >= 0.3:
            into.append([last, now - last - tick])
        last = now


def answer_gaps(records_path: str, pauses: list, floor: float = 0.5) -> list:
    """Stretches of ``floor`` seconds or more in which no operation of the
    window ended: seconds after the window's first request, length, and how
    much of it this process was paused too."""
    with open(records_path) as f:
        rows = [json.loads(line) for line in f]
    rows = [r for r in rows if r.get("phase") == "window" and "t_end" in r]
    if not rows:
        return []
    t0 = min(r["t_start"] for r in rows)
    ends = sorted(r["t_end"] for r in rows)
    out = []
    for a, b in zip(ends, ends[1:]):
        if b - a >= floor:
            shared = sum(max(0.0, min(b, t + d) - max(a, t)) for t, d in pauses)
            out.append({"at_s": a - t0, "seconds": b - a, "measure_py_paused_s": shared})
    return out


def main() -> int:
    tag, specs = sys.argv[1], sys.argv[2:]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out_dir = os.path.join(REPO, "chiprun_out", "bench", tag)
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for i, spec in enumerate(specs):
        parts = spec.split(":")
        cell, seed, trace = parts[0], parts[1], parts[2]
        seconds = parts[3] if len(parts) > 3 and parts[3] else str(bench["run_seconds"])
        argv = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
                "--seed", seed, "--seconds", seconds, "--trace", trace]
        env = dict(os.environ)
        if "control" in parts[4:]:
            argv.append("--control")
        for part in parts[4:]:
            if part.startswith("fault="):
                env["BENCH_FAULT"] = part[len("fault="):]
                argv[1:2] = ["-c", "import sys; sys.path.insert(0, sys.argv.pop(1)); "
                             "import run; sys.exit(run.main(sys.argv[2:], "
                             "launcher=sys.argv[1]))", HERE,
                             os.path.join(HERE, "tests", "faulty_serve.py")]
        t0 = time.monotonic()
        pauses: list = []
        stop = threading.Event()
        watcher = threading.Thread(target=watch_pauses, args=(pauses, stop), daemon=True)
        watcher.start()
        proc = subprocess.run(argv, cwd=REPO, capture_output=True, text=True, env=env)
        stop.set()
        watcher.join()
        wall = time.monotonic() - t0
        last = proc.stdout.strip().splitlines()[-1:] or [""]
        try:
            result = json.loads(last[0])
        except ValueError:
            result = None
        row = {"spec": spec, "rc": proc.returncode, "wall_s": round(wall, 1),
               "result": result}
        rows.append(row)
        with open(os.path.join(out_dir, "runs.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
        with open(os.path.join(out_dir, f"{i:02d}.stderr"), "w") as f:
            f.write(proc.stderr)
        logs = os.path.join(HERE, "_out", cell)
        sound = result is not None and result.get("correct")
        for name in os.listdir(logs) if os.path.isdir(logs) else []:
            if name == "records.jsonl" or not sound:
                if name.endswith((".log", ".jsonl", ".json")):
                    shutil.copy(os.path.join(logs, name),
                                os.path.join(out_dir, f"{i:02d}.{name}"))
        brief = {k: round(v["value"], 6) for k, v in (result or {}).get("metrics", {}).items()}
        records = os.path.join(logs, "records.jsonl")
        halves = half_tails(records) if sound and os.path.isfile(records) else None
        summary = json.dumps({
            "spec": spec, "rc": proc.returncode, "wall_s": row["wall_s"],
            "correct": (result or {}).get("correct"),
            "attempted": (result or {}).get("attempted"),
            "device": (result or {}).get("device"), "metrics": brief,
            "p90_halves": halves,
            "answer_gaps": answer_gaps(records, pauses) if os.path.isfile(records) else None,
            "herd_reboots": proc.stderr.count('"event": "herd_reboot"')})
        print(summary, flush=True)
        with open(os.path.join(out_dir, "summary.jsonl"), "a") as f:
            f.write(summary + "\n")
        if result is None:
            print(proc.stderr[-3000:], flush=True)
    by_cell: dict[str, dict[str, list[float]]] = {}
    for row in rows:
        if row["result"] and ":0" in row["spec"]:
            cell = row["spec"].split(":")[0]
            for name, m in row["result"]["metrics"].items():
                by_cell.setdefault(cell, {}).setdefault(name, []).append(m["value"])
    for cell, metrics in by_cell.items():
        for name, values in metrics.items():
            if len(values) >= 3:
                print(json.dumps({"cell": cell, "metric": name, "n": len(values),
                                  "median": statistics.median(values),
                                  "spread": spread(values),
                                  "spread_as_driver": trimmed_spread(values),
                                  "values": values}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
