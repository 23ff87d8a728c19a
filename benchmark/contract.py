"""The benchmark's contract, as far as a file and a last line can be held
to it. ``run.py`` runs ``check_last_line`` on its own result before printing
it; ``selfcheck.py`` runs both checks without a chip."""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end ones untraced,
    its per-layer ones traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _line(text) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def check_benchmark(bench: dict) -> list[str]:
    bad = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != want:
        bad.append(f"keys {sorted(bench)} are not {sorted(want)}")
        return bad
    if not (isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51):
        bad.append("run_seconds is not a whole number from 1 to 51")
    for word in bench["command"]:
        if not _line(word) or word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r}")
    paths = bench["paths"]

    def under_paths(path: str) -> bool:
        return any(path.startswith(p.rstrip("/") + "/") for p in paths)

    names = set()
    for config in bench["configs"]:
        if set(config) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(config)}")
            continue
        if not NAME.match(config["name"]) or config["name"] in names:
            bad.append(f"config name {config['name']!r}")
        names.add(config["name"])
        if not under_paths(config["file"]) or not os.path.isfile(
                os.path.join(REPO, config["file"])):
            bad.append(f"config file {config['file']!r}")
        else:
            with open(os.path.join(REPO, config["file"])) as f:
                doc = json.load(f)
            for key in config["reduced"]:
                if not NAME.match(key) or key not in doc:
                    bad.append(f"reduced key {key!r} of {config['name']}")
            if sorted(doc.get("reduced", [])) != sorted(config["reduced"]):
                bad.append(f"{config['file']} lists other reduced keys")
        if not _line(config["source"]) or not _line(config["why"]):
            bad.append(f"source or why of {config['name']}")
    cells = set()
    pairs = set()
    for cell in bench["workloads"]:
        if set(cell) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(cell)}")
            continue
        for key in ("name", "config", "traffic"):
            if not NAME.match(cell[key]):
                bad.append(f"workload {key} {cell[key]!r}")
        if cell["name"] in cells or (cell["config"], cell["traffic"]) in pairs:
            bad.append(f"workload {cell['name']!r} appears twice")
        cells.add(cell["name"])
        pairs.add((cell["config"], cell["traffic"]))
        if cell["config"] not in names:
            bad.append(f"workload {cell['name']} names no configuration")
        if cell["chips"] not in (1, 4) or not _line(cell["why"]):
            bad.append(f"chips or why of {cell['name']}")
        if not os.path.isfile(os.path.join(HERE, "traffic", cell["traffic"] + ".json")):
            bad.append(f"no traffic file for {cell['name']}")
    for config in bench["configs"]:
        if not any(c["config"] == config["name"] for c in bench["workloads"]):
            bad.append(f"configuration {config['name']} is used by no cell")
    metric_names = set()
    e2e = {m.get("name") for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for group, keys in (
        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
    ):
        for m in bench[group]:
            if set(m) - {"workloads"} != keys:
                bad.append(f"{group} keys {sorted(m)}")
                continue
            if not NAME.match(m["name"]) or m["name"] in metric_names:
                bad.append(f"metric name {m['name']!r}")
            metric_names.add(m["name"])
            if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
                bad.append(f"unit or better of {m['name']}")
            if m["source"] not in SOURCES:
                bad.append(f"source of {m['name']}")
            if group == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"end-to-end source of {m['name']}")
                if not 0.01 <= m["bound"] <= 0.25:
                    bad.append(f"bound of {m['name']}")
            else:
                if not _line(m["layer"]) or m["moves"] not in e2e:
                    bad.append(f"layer or moves of {m['name']}")
            for cell in m.get("workloads", []):
                if cell not in cells:
                    bad.append(f"{m['name']} lists unknown cell {cell}")
            if not os.path.isfile(os.path.join(HERE, "metrics", m["name"] + ".json")):
                bad.append(f"no metrics/{m['name']}.json")
    for cell in cells:
        got = {m["name"] for m in metrics_of(bench, cell, False)}
        if "setup_s" not in got or len(got) < 2:
            bad.append(f"{cell} reports too few end-to-end metrics")
        for m in metrics_of(bench, cell, True):
            if m["moves"] not in got:
                bad.append(f"{m['name']} moves {m['moves']}, which {cell} lacks")
        if not metrics_of(bench, cell, True):
            bad.append(f"{cell} reports no per-layer metric")
    return bad


def check_last_line(line: str, bench: dict, cell: str, trace: bool,
                    chips: int) -> list[str]:
    """What keeps ``line`` from being the contract's result of this run."""
    bad = []
    if "\n" in line:
        return ["the result is not one line"]
    try:
        doc = json.loads(line)
    except ValueError as e:
        return [f"not JSON: {e}"]
    if not isinstance(doc, dict):
        return ["not a JSON object"]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in doc:
            bad.append(f"key {key!r} is missing")
    if bad:
        return bad
    if not isinstance(doc["correct"], bool):
        bad.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(doc[key], int) or isinstance(doc[key], bool) or doc[key] < 0:
            bad.append(f"{key} is not a count")
    want = {m["name"]: m["unit"] for m in metrics_of(bench, cell, trace)}
    metrics = doc["metrics"]
    if not isinstance(metrics, dict):
        return bad + ["metrics is not an object"]
    for name, unit in want.items():
        if name not in metrics:
            bad.append(f"metric {name} is missing")
            continue
        m = metrics[name]
        if not isinstance(m, dict) or m.get("unit") != unit:
            bad.append(f"metric {name} does not carry the unit {unit}")
            continue
        v = m.get("value")
        if isinstance(v, bool) or not isinstance(v, (int, float)) or v != v \
                or v in (float("inf"), float("-inf")):
            bad.append(f"metric {name} has no finite value: {v!r}")
        elif name.endswith("_roofline") or "_roofline." in name or "mfu" in name:
            if not 0 < v <= 105:
                bad.append(f"metric {name} = {v} is not a share above 0 and under 105")
    for name in metrics:
        if name not in want:
            bad.append(f"metric {name} is not one of this run's")
        if not NAME.match(name):
            bad.append(f"metric name {name!r}")
    device = doc["device"]
    if not isinstance(device, dict):
        return bad + ["device is not an object"]
    if device.get("platform") != "tpu":
        bad.append(f"device.platform is {device.get('platform')!r}, not 'tpu'")
    if not isinstance(device.get("kind"), str) or not device.get("kind"):
        bad.append("device.kind is missing")
    if device.get("count") != chips:
        bad.append(f"device.count is {device.get('count')!r}, the cell asks for {chips}")
    peak = device.get("memory_peak_bytes")
    if not isinstance(peak, int) or isinstance(peak, bool) or peak <= 0:
        bad.append(f"device.memory_peak_bytes is {peak!r}")
    if trace:
        busy, window = device.get("busy_s"), device.get("window_s")
        if not all(isinstance(x, (int, float)) and not isinstance(x, bool)
                   for x in (busy, window)):
            bad.append("device.busy_s or device.window_s is missing")
        elif not 0 < busy <= window:
            bad.append(f"not 0 < busy_s ({busy}) <= window_s ({window})")
        breakdown = doc.get("breakdown")
        if breakdown is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = breakdown.get(key)
                if not isinstance(rows, list) or len(rows) > 10 or not all(
                    isinstance(r, list) and len(r) == 2 and isinstance(r[0], str)
                    and isinstance(r[1], (int, float)) for r in rows
                ):
                    bad.append(f"breakdown.{key} is not at most 10 [name, seconds]")
    keys = list(doc)
    if "checks" in doc and keys[-1] != "checks":
        bad.append("the numbers compared do not come last")
    return bad
