"""Reader kinds: one module each, ``read(ctx, params) -> float | None``.

A per-layer or end-to-end metric is a file ``metrics/<name>.json`` that names
a kind and gives its parameters; the harness imports ``readers/<kind>.py``.
A reader that finds nothing to read returns None and the harness leaves the
metric out of the line. ``ctx`` is what one run observed:

    window   {"t_open", "t_close", "seconds", "bytes_moved", "bytes_done"}
             (monotonic clock; payload on the wire, and of operations ended,
             between the open and the close)
    records  finished operations of the window phase, each with its spans
             (a pull also with the ``agent`` role that delivered it)
    harness  {"ready_s", "warm_s", "setup_s", "gen_busy_s", "clients"}
             (``clients``: the operations in flight at once; in a rollout
             its clients times the herd's agents)
    prom     {component: {"before": text, "after": text}}   /metrics scrapes
             of each role a counter check or metric file names, the chip's,
             and in a rollout every role of the herd (``agent-1``, ...)
    trace    None, or {"busy_s", "window_s", "payload_bytes", ...}
    peaks    the device's row of peaks.json
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def read_metric(name: str, ctx: dict):
    with open(os.path.join(os.path.dirname(HERE), "metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("readers." + spec["kind"])
    return reader.read(ctx, spec)


def answered(ctx: dict) -> list[dict]:
    """Every operation the window started and the system answered, those
    drained after the close too. A wrong answer is still an answer: it
    fails ``correct``, and the run's times stay what they were."""
    return [r for r in ctx["records"] if r["fault"] != "unanswered"]


def prom_value(text: str, name: str, labels: dict) -> float:
    """Sum of the samples of ``name`` whose labels include ``labels``."""
    total = 0.0
    for line in text.splitlines():
        if not (line.startswith(name + "{") or line.startswith(name + " ")):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def prom_delta(ctx: dict, component: str, name: str, labels: dict) -> float | None:
    scrapes = ctx["prom"].get(component)
    if scrapes is None:
        return None
    return (prom_value(scrapes["after"], name, labels)
            - prom_value(scrapes["before"], name, labels))
