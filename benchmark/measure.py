#!/usr/bin/env python3
"""Several runs of run.py in one call, for the builder's chip sessions:

    chiprun -- python3 benchmark/measure.py <tag> <cell>:<seed>:<trace>[:<seconds>[:control|fault=<name>]] ...

Each run is a process of its own, one after another (``control``: the chip's
component hashes on the host; ``fault=<name>``: the served path is broken by
``tests/faulty_serve.py``; both have to come out not correct). Result lines, the
earlier lines and, for a run that printed no result, the herd's logs go to
``chiprun_out/bench/<tag>/``; a summary of every run is printed at the end.
Also reduces sets of runs to the spread the contract's bounds are set from.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


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
        proc = subprocess.run(argv, cwd=REPO, capture_output=True, text=True, env=env)
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
        brief = {k: round(v["value"], 4) for k, v in (result or {}).get("metrics", {}).items()}
        print(json.dumps({"spec": spec, "rc": proc.returncode, "wall_s": row["wall_s"],
                          "correct": (result or {}).get("correct"),
                          "attempted": (result or {}).get("attempted"),
                          "device": (result or {}).get("device"), "metrics": brief}),
              flush=True)
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
                                  "spread": spread(values), "values": values}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
