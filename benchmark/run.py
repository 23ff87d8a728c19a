#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the cell's herd through the CLI (the component its configuration
gives the chip goes through ``serve.py``), warms up with one deal of the
traffic's deck, measures a closed loop for ``--seconds``, drains and checks
what is in flight, reads pushed blobs back, holds the serving process's own
counters to the configuration's guarantees, and prints one JSON object as
the last line of standard output. Every other line (phases, lateness, trace
file size, per-request records' path) goes to standard error or to
``benchmark/_out/<cell>/``.

``--scale tiny`` is the CPU rehearsal: every phase runs, the traced path
included, and the run then fails the device check and prints no result.
"""

from __future__ import annotations

import argparse
import asyncio
import glob
import json
import os
import signal
import subprocess
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import contract  # noqa: E402
import traffic  # noqa: E402
from herd import Herd, HerdError  # noqa: E402
from load import WARM_INDEX, Load  # noqa: E402
from readers import prom_delta, read_metric  # noqa: E402

# XLA's modules and operations only: a third of the default mode's cost an event.
TRACE_OPTIONS = {"advanced_configuration": {"tpu_trace_mode": "TRACE_ONLY_XLA"}}


class RunError(Exception):
    pass


def say(**doc) -> None:
    """An earlier line: never the result."""
    print(json.dumps(doc), file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def disk_written_bytes() -> int:
    """Sectors written to whole block devices so far (/proc/diskstats)."""
    total = 0
    try:
        with open("/proc/diskstats") as f:
            for line in f:
                p = line.split()
                if os.path.exists(f"/sys/block/{p[2]}"):
                    total += int(p[9]) * 512
    except (OSError, IndexError, ValueError):
        pass
    return total


def counter_checks(config: dict, ctx: dict, payload: dict) -> dict:
    """The configuration's guarantees about the serving process's own
    counters: the device path, not a host fallback, did the work."""
    out = {}
    for check in config["counter_checks"]:
        delta = prom_delta(ctx, check["component"], check["metric"], check["labels"])
        if check["rule"] == "delta_at_most":
            value = delta
        elif check["rule"] == "covers_payload_bytes":
            value = max(0.0, payload["bytes"] - delta)
        elif check["rule"] == "covers_payload_pieces":
            value = max(0.0, payload["pieces"] - delta)
        else:
            raise RunError(f"unknown counter rule {check['rule']!r}")
        out[check["name"]] = {"value": value, "limit": check["limit"]}
    return out


async def traced_stretch(herd: Herd, plan: dict, trace_dir: str,
                         t_open: float, seconds: float, into: dict) -> None:
    """Trace the window's last ``plan["stretch_s"]`` seconds of measured
    traffic (never more than half the window: the rehearsal's is 6 s) from
    inside the process that holds the chip: one steady stretch under the
    full load, asked to stop just before the close, so that the tracer
    writes its file while the window's stragglers drain and not inside the
    window. The stretch has to hold the work its readers divide by: on a
    chip that the host leaves idle 85-99% of the time 0.3 s held anything
    from none to fifteen pushes, and a stretch that holds no device work
    fails the run. Five seconds of the push cell hold about 230 pushes and
    12,000 device events (a slab call is one event), and ``stop_trace``
    then takes about 6 s, half a millisecond an event (PERF.md section 3).
    The window's own numbers are taken before the tracer writes."""
    stretch_s = min(plan["stretch_s"], seconds / 2)
    await asyncio.sleep(max(0.0, t_open + seconds - stretch_s - 0.25 - time.monotonic()))
    into["dir"] = trace_dir
    into.update(await asyncio.to_thread(
        herd.control.ask, op="start_trace", dir=trace_dir, options=TRACE_OPTIONS))
    await asyncio.sleep(stretch_s)
    into.update(await asyncio.to_thread(herd.control.ask, op="stop_trace"))


def label_gaps(reduced: dict, stretch: dict, records: list[dict]) -> list:
    """The idle gaps of one stretch, each named by the client phase that
    covered most of it. The trace's clock is set against the host's at the
    ``bench_trace_open`` annotation."""
    offset = stretch["t_open"] - reduced["t_open_ns"] / 1e9
    spans = []
    for r in records:
        if r["op"] == "push" and "t_committed" in r:
            spans += [("patch", r["t_start"], r["t_patched"]),
                      ("commit", r["t_patched"], r["t_committed"]),
                      ("metainfo", r["t_committed"], r["t_end"])]
        elif "t_end" in r:
            spans.append((r["op"], r["t_start"], r["t_end"]))
    out = []
    for a_ns, b_ns in reduced["gaps_ns"]:
        a, b = a_ns / 1e9 + offset, b_ns / 1e9 + offset
        cover: dict[str, float] = {}
        for name, s, e in spans:
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                cover[name] = cover.get(name, 0.0) + overlap
        name = max(cover, key=cover.get) if cover else "none"
        out.append([name, (b_ns - a_ns) / 1e9])
    return out


async def drive(args, cell: dict, herd: Herd, config: dict, mix: dict,
                out_dir: str) -> dict:
    """Everything between the herd's READY lines and its stop."""
    t_ready = time.monotonic()
    describe = herd.control.ask(op="describe")
    device = {k: describe[k] for k in ("platform", "kind", "count")}
    say(event="device", device=device, ready=herd.ready.get(herd.chip_role))
    on_chip = device["platform"] == "tpu" and device["count"] == cell["chips"]
    if not on_chip and args.scale == "real":
        raise RunError(f"the chip's component sees {device}, not {cell['chips']} TPU chip(s)")

    sizes = traffic.deal(mix)
    load = Load(herd, config, mix, args.seed, sizes)
    await load.open()
    try:
        if load.op == "pull":
            t = time.monotonic()
            await load.seed_pool()
            say(event="seeded", blobs=len(load.pool), seconds=time.monotonic() - t)
        t = time.monotonic()
        n_warm = mix["warmup_decks"] * len(sizes)
        await load.run_phase("warmup", None, n_warm, WARM_INDEX)
        warm_s = time.monotonic() - t
        cold = [r for r in load.records if r["fault"] == "unanswered"]
        if cold:
            raise RunError(f"warm-up: {len(cold)} operations got no answer, "
                           f"first: {cold[0]['why']}")
        warmed = herd.control.ask(op="describe")
        say(event="warmed", operations=n_warm, seconds=warm_s,
            compiles=warmed["compiles"], compile_s=warmed["compile_s"])

        roles = sorted({c["component"] for c in config["counter_checks"]}
                       | {herd.chip_role})
        prom = {role: {"before": await load.metrics_text(role)} for role in roles}
        stretch: dict = {}
        on_open = None
        if args.trace:
            def on_open(t_open):
                return traced_stretch(herd, config["trace"], os.path.join(out_dir, "trace"),
                                      t_open, args.seconds, stretch)
        disk0, gen0 = disk_written_bytes(), load.gen_s
        setup_s = time.monotonic() - T0
        t_open, t_close = await load.run_phase(
            "window", args.seconds, None, 0, on_open)
        drained_s = time.monotonic() - t_close
        gen_busy_s = load.gen_s - gen0
        for role in roles:
            prom[role]["after"] = await load.metrics_text(role)
        after = herd.control.ask(op="describe")
        readback = await load.read_back() if load.op == "push" else []
    finally:
        await load.close()

    records = [r for r in load.records if r["phase"] == "window"]
    ctx = {
        # bytes_moved: payload on the wire between the open and the close.
        # bytes_done: payload of the operations that ended between them.
        "window": {"t_open": t_open, "t_close": t_close, "seconds": t_close - t_open,
                   "bytes_moved": load.phase_bytes,
                   "bytes_done": sum(r["bytes"] for r in records
                                     if r["ok"] and r["t_end"] <= t_close)},
        "records": records,
        "harness": {"ready_s": t_ready - T0, "warm_s": warm_s, "setup_s": setup_s,
                    "gen_busy_s": gen_busy_s, "clients": load.clients},
        "prom": prom,
        "trace": None,
    }
    payload = {"bytes": sum(r["bytes"] for r in records if r["ok"]),
               "pieces": sum(r.get("pieces", 0) for r in records if r["ok"])}
    checks = {
        "unanswered": {
            "value": sum(1 for r in records if r["fault"] == "unanswered"), "limit": 0},
        # Warm-up answers are held to the reference too.
        "wrong_answers": {
            "value": sum(1 for r in load.records if r["fault"] == "wrong"), "limit": 0},
    }
    if load.op == "push":
        checks["readback_mismatches"] = {
            "value": sum(1 for r in readback if not r["ok"]), "limit": 0}
        checks["readback_blobs_short"] = {
            "value": max(0, min(1, len(records)) - len(readback)), "limit": 0}
    checks.update(counter_checks(config, ctx, payload))
    say(event="window", attempted=len(records),
        ended_in_window=sum(1 for r in records if r["t_end"] <= t_close),
        drained_s=drained_s, read_back=len(readback),
        payload_bytes=payload["bytes"], disk_written_bytes=disk_written_bytes() - disk0,
        compiles_in_window=after["compiles"] - warmed["compiles"],
        compile_s_in_window=after["compile_s"] - warmed["compile_s"],
        faults=[r["why"] for r in records + readback if not r["ok"]][:5])
    with open(os.path.join(out_dir, "records.jsonl"), "w") as f:
        for r in load.records:
            f.write(json.dumps(r) + "\n")
    return {"ctx": ctx, "checks": checks, "records": records, "stretch": stretch,
            "device": {**device, "memory_peak_bytes": after["memory_peak_bytes"]},
            "on_chip": on_chip}


def _reduce_on_cpu(path: str, out: str) -> dict:
    subprocess.run(
        [sys.executable, os.path.join(HERE, "reduce_trace.py"), path, out],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=200, check=False,
    )
    with open(out) as f:
        return json.load(f)


def reduce_trace(run: dict, out_dir: str) -> dict:
    """After the servers have exited: the stretch's xplane, read in a
    child on the CPU (this process never imports JAX)."""
    stretch = run["stretch"]
    if "t_written" not in stretch:
        raise RunError("no stretch of the window was traced")
    found = sorted(glob.glob(os.path.join(
        stretch["dir"], "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RunError(f"the traced stretch left no xplane under {stretch['dir']}")
    out = os.path.join(out_dir, "trace_reduced.json")
    reduced = _reduce_on_cpu(found[-1], out)
    if not reduced.get("device_planes") and not run["on_chip"]:
        # The CPU rehearsal's trace has no device plane. Read the recorded
        # fixture in its place, so that the steps after this one run too.
        say(event="rehearsal", note="no device plane: reducing the fixture instead")
        reduced = _reduce_on_cpu(
            os.path.join(HERE, "fixtures", "one_upload.xplane.pb"), out)
    write_s = stretch["t_written"] - stretch["t_close"]
    say(event="trace", file_bytes=reduced["file_bytes"], seconds_to_write=write_s,
        asked_s=stretch["t_close"] - stretch["t_open"],
        window_from=reduced.get("window_from"), busy_line=reduced.get("busy_line"),
        busy_s=reduced.get("busy_s"), window_s=reduced.get("window_s"),
        events=reduced.get("n_events"))
    if "error" in reduced:
        raise RunError(f"trace reduction: {reduced['error']}")
    if not reduced["device_planes"] or not reduced["busy_s"] > 0:
        raise RunError("no operation ran on the device in the traced stretch of "
                       f"{reduced['window_s']} s")
    window = run["ctx"]["window"]
    return {
        "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
        "device_ops": reduced["device_ops"][:10],
        "idle_gaps": sorted(label_gaps(reduced, stretch, run["records"]),
                            key=lambda g: -g[1])[:10],
        # The payload the device had to hash while it was watched: the
        # window's own rate of completed payload (open to close, nothing of
        # the drain or of the tracer's writing in it), over the traced
        # seconds. A second is too short to count whole blobs in.
        "payload_bytes": window["bytes_done"] / window["seconds"] * reduced["window_s"],
    }


def result_line(args, bench: dict, cell: dict, run: dict) -> str:
    ctx = run["ctx"]
    metrics = {}
    for m in contract.metrics_of(bench, cell["name"], bool(args.trace)):
        value = read_metric(m["name"], ctx)
        if value is None:
            raise RunError(f"metric {m['name']} found nothing to read in this run")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = run["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    records = run["records"]
    doc = {
        "correct": correct,
        "attempted": len(records),
        "failed": sum(1 for r in records if not r["ok"]),
        "metrics": metrics,
        "device": dict(run["device"]),
    }
    trace = ctx["trace"]
    if trace:
        doc["device"]["busy_s"] = trace["busy_s"]
        doc["device"]["window_s"] = trace["window_s"]
        doc["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    doc["checks"] = checks
    return json.dumps(doc)


def main(argv: list[str] | None = None, require_chip: bool = True,
         launcher: str | None = None, bench_path: str | None = None) -> int:
    """``require_chip``, ``launcher`` and ``bench_path`` are for the tests
    under ``benchmark/tests``: they drive a whole run on the CPU, once with
    the served path broken underneath by a launcher of their own, once
    with the entries a later PR would add to BENCHMARK.json."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", choices=("real", "tiny"), default="real",
                    help="tiny: the CPU rehearsal's sizes (default: the real sizes)")
    ap.add_argument("--control", action="store_true",
                    help="run the control: the chip's component hashes on the "
                         "host (--hasher cpu); correct has to come out false")
    args = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(REPO, "kraken_tpu", "cli.py")):
        say(event="failed", error="no kraken_tpu/cli.py beside benchmark/: "
            "there is no system under test in this directory")
        return 2
    bench = contract.load_benchmark(bench_path)
    cell = contract.cell_of(bench, args.workload)
    config = load_json("configs", cell["config"] + ".json")
    mix = traffic.load_traffic(cell["traffic"], args.scale)
    peaks = load_json("peaks.json")
    out_dir = os.path.join(HERE, "_out", cell["name"])
    herd = Herd(config, os.path.join(HERE, "_work", cell["name"]), out_dir,
                chip_hasher="cpu" if args.control else None, launcher=launcher)
    # A run that is told to end stops its herd on the way out.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    say(event="start", cell=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, scale=args.scale, host_cpus=os.cpu_count())
    try:
        try:
            herd.start(timeout=300)
            run = asyncio.run(drive(args, cell, herd, config, mix, out_dir))
        finally:
            herd.stop()
        kind = run["device"]["kind"]
        if kind in peaks:
            run["ctx"]["peaks"] = peaks[kind]
        elif run["on_chip"]:
            raise RunError(f"no peaks for device kind {kind!r} in peaks.json")
        else:  # the rehearsal goes on, and fails the device check at the end
            run["ctx"]["peaks"] = next(iter(peaks.values()))
        if args.trace:
            run["ctx"]["trace"] = reduce_trace(run, out_dir)
        line = result_line(args, bench, cell, run)
    except (RunError, HerdError, asyncio.TimeoutError) as e:
        say(event="failed", error=f"{type(e).__name__}: {e}")
        return 1
    for name, c in run["checks"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    problems = contract.check_last_line(
        line, bench, cell["name"], bool(args.trace), cell["chips"])
    if "jax" in sys.modules:
        problems.append("the parent imported jax")
    if not run["on_chip"] and not require_chip:
        problems = [p for p in problems if not p.startswith("device.")]
    if problems:
        say(event="failed", error="the result does not meet the contract",
            problems=problems, would_be=json.loads(line))
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
