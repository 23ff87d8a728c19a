#!/usr/bin/env python3
"""The step table of benchmark runs, a push or a piece, on the chip:

    chiprun --timeout 1500 -- python3 chip_push_steps.py <tag> \\
        <cell>:<seed>[:control][:tiny|:nochip][:trace][:root=<dir>] ...

Each spec is one whole run of ``benchmark/run.py`` (a process of its own,
one after another; ``control``: the origin hashes on the host; ``tiny``:
the CPU rehearsal's sizes, no chip needed; ``nochip``: the real sizes with
the device check lifted, for a sandbox: with ``control`` it is the host
path alone, and none of its numbers is the chip's; ``root=<dir>``: the
benchmark and the program of another checkout, such as a parent commit
unpacked with ``git archive``, reduced by this one), with the two
``/metrics`` scrapes of the component that holds the chip (before the
window, after the drain: the ones the harness takes for its own readers)
kept and reduced by ``kraken_tpu.utils.pushsteps.push_step_table``: CPU,
switches, and count, cpu, wall and wall - cpu of every step, a unit of
work: a push (``create``) on an origin, a piece received
(``p2p.payload``) on an agent. Scrapes,
tables and result lines go to ``chiprun_out/push_steps/<tag>/``; every
table and a mean over the sound runs of each kind are printed at the end.
The benchmark's files are used as they are: this adds nothing to a run
but the two files it writes after the window.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from kraken_tpu.utils.pushsteps import push_step_table, render_table  # noqa: E402

# The child: benchmark/run.py's own main, with drive() made to leave the
# two scrapes of every component it reads behind.
CHILD = """
import json, os, sys
sys.path.insert(0, sys.argv.pop(1))
keep = sys.argv.pop(1)
mode = sys.argv.pop(1)
import run
if mode == "nochip":
    # The device check belongs to --scale real: ask for the rehearsal and
    # deal it the real sizes.
    load_traffic = run.traffic.load_traffic
    run.traffic.load_traffic = lambda name, scale: load_traffic(name, "real")
drive = run.drive
async def keeping(*args, **kwargs):
    out = await drive(*args, **kwargs)
    with open(keep, "w") as f:
        json.dump(out["ctx"]["prom"], f)
    return out
run.drive = keeping
sys.exit(run.main(sys.argv[1:], require_chip=mode == "chip"))
"""


def mean_table(tables: list[dict]) -> dict:
    """Field by field over runs of one kind (steps matched by name and
    class; a step that a run lacks counts as nothing there)."""
    n = len(tables)
    out = {"pushes": sum(t["pushes"] for t in tables) / n,
           "unit": tables[0]["unit"], "cpu_s": {}, "switches": {},
           "classes": {}, "steps": []}
    for field in ("cpu_s", "switches"):
        for t in tables:
            for k, v in t[field].items():
                out[field][k] = out[field].get(k, 0.0) + v / n
    for t in tables:
        for cls, row in t["classes"].items():
            into = out["classes"].setdefault(cls, {})
            for k, v in row.items():
                if k != "coverage":
                    into[k] = into.get(k, 0.0) + v / n
    for row in out["classes"].values():
        bill = row.get("user", 0.0) + row.get("system", 0.0)
        row["coverage"] = row["steps_cpu"] / bill if bill else None
    steps: dict[tuple, dict] = {}
    for t in tables:
        for r in t["steps"]:
            into = steps.setdefault((r["step"], r["class"]), {
                "step": r["step"], "class": r["class"], "entries_a_push": 0.0,
                "wall_s": 0.0, "cpu_s": None, "waiting_s": None})
            into["entries_a_push"] += r["entries_a_push"] / n
            into["wall_s"] += r["wall_s"] / n
            for k in ("cpu_s", "waiting_s"):
                if r[k] is not None:
                    into[k] = (into[k] or 0.0) + r[k] / n
    out["steps"] = sorted(steps.values(), key=lambda r: -(r["cpu_s"] or 0.0))
    return out


def main() -> int:
    tag, specs = sys.argv[1], sys.argv[2:]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = str(bench["run_seconds"])
    out_dir = os.path.join(REPO, "chiprun_out", "push_steps", tag)
    os.makedirs(out_dir, exist_ok=True)
    kinds: dict[str, list[dict]] = {}
    for i, spec in enumerate(specs):
        cell, seed, *flags = spec.split(":")
        root = os.path.abspath(next(
            (f[len("root="):] for f in flags if f.startswith("root=")), REPO))
        config = next(c for c in bench["configs"] if c["name"] == next(
            w["config"] for w in bench["workloads"] if w["name"] == cell))
        with open(os.path.join(REPO, config["file"])) as f:
            component = json.load(f)["chip"]
        unit = "create" if component == "origin" else "p2p.payload"
        keep = os.path.join(out_dir, f"{i:02d}.scrapes.json")
        mode = next((m for m in ("tiny", "nochip") if m in flags), "chip")
        argv = [sys.executable, "-c", CHILD, os.path.join(root, "benchmark"),
                keep, mode,
                "--workload", cell, "--seed", seed,
                "--seconds", "6" if mode == "tiny" else seconds,
                "--trace", "1" if "trace" in flags else "0"]
        if mode != "chip":
            argv += ["--scale", "tiny"]
        if "control" in flags:
            argv.append("--control")
        proc = subprocess.run(argv, cwd=root, capture_output=True, text=True)
        with open(os.path.join(out_dir, f"{i:02d}.stderr"), "w") as f:
            f.write(proc.stderr)
        try:
            result = json.loads(proc.stdout.strip().splitlines()[-1])
        except (IndexError, ValueError):
            result = None
        brief = {"spec": spec, "rc": proc.returncode,
                 "correct": (result or {}).get("correct"),
                 "attempted": (result or {}).get("attempted"),
                 "failed": (result or {}).get("failed"),
                 "checks": {k: v["value"] for k, v in
                            (result or {}).get("checks", {}).items()},
                 "metrics": {k: v["value"] for k, v in
                             (result or {}).get("metrics", {}).items()}}
        print(json.dumps(brief), flush=True)
        if not os.path.isfile(keep):
            print(proc.stderr[-3000:], flush=True)
            continue
        with open(keep) as f:
            scrapes = json.load(f)[component]
        table = push_step_table(scrapes["before"], scrapes["after"], unit)
        with open(os.path.join(out_dir, f"{i:02d}.table.json"), "w") as f:
            json.dump({**brief, "table": table}, f)
        print(render_table(table), flush=True)
        # The control has to come out not correct; any other run, correct.
        kind = ("control" if "control" in flags else "device") + (
            "" if root == REPO else "." + os.path.basename(os.path.normpath(root)))
        if table["pushes"] and (mode != "chip" or (
                result is not None and result["correct"] == (kind == "device"))):
            kinds.setdefault(kind, []).append(table)
    for kind, tables in kinds.items():
        if len(tables) > 1:
            print(f"== mean of {len(tables)} {kind} runs ==")
            mean = mean_table(tables)
            with open(os.path.join(out_dir, f"mean.{kind}.json"), "w") as f:
                json.dump(mean, f)
            print(render_table(mean), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
