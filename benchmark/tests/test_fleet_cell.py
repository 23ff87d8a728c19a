"""The fleet cell's rehearsal: whole runs of run.py on the CPU for
``agent-tpu-fleet.pull-layers`` as BENCHMARK.json ships it, at the traffic
file's ``tiny`` deck (three agents, the chip's through ``serve.py``, each
capped at 300 MB/s both ways; rollouts of four blobs of 4.5-14 MB, each
pulled through all three agents at once), the look for a chip skipped. A
sound run is correct, a traced one prints every per-layer metric
BENCHMARK.json enters for the cell (the six fleet entries among them, with
agents serving each other), and the control (the chip's agent verifying on
the host) is not correct. The fleet reader reads 0.0, not nothing, on a
program that does not export the serve stages.

    python3 -m pytest benchmark/tests/test_fleet_cell.py -q   (about four minutes)

The benchmark's own runs do not run these.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import contract  # noqa: E402
import run  # noqa: E402
from readers import read_metric  # noqa: E402

CELL = "agent-tpu-fleet.pull-layers"
FLEET = ("peer_served_share.fleet", "serve_admit_s.fleet", "serve_read_s.fleet",
         "serve_queue_s.fleet", "serve_shape_s.fleet", "serve_write_s.fleet")
SHIPPED = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")


def one_run(seed, trace, *extra, seconds=8):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace), "--scale", "tiny", *extra],
            require_chip=False, bench_path=SHIPPED,
        )
    assert rc == 0, "the run printed no result"
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_shipped_cell_sound_run_is_correct():
    bench = contract.load_benchmark(SHIPPED)
    assert contract.check_benchmark(bench) == []
    assert contract.cell_of(bench, CELL)["chips"] == 1
    doc = one_run(4200004210, 0)
    assert doc["correct"] is True and doc["failed"] == 0 and doc["attempted"] > 0
    assert set(doc["checks"]) == {
        "unanswered", "wrong_answers", "host_verify_batches", "device_pieces_short",
        "device_bytes_short", "agent-1_pieces_short", "agent-2_pieces_short"}
    assert all(c["value"] == 0 and c["limit"] == 0 for c in doc["checks"].values())
    assert set(doc["metrics"]) == {"pull_rate", "setup_s"}


def test_traced_rehearsal_prints_every_per_layer_metric():
    bench = contract.load_benchmark(SHIPPED)
    entered = {m["name"]: m for m in contract.metrics_of(bench, CELL, True)}
    assert set(FLEET) <= set(entered) and "hash_hbm_roofline.pull" not in entered
    for name in FLEET:
        assert entered[name]["workloads"] == [CELL] and entered[name]["moves"] == "pull_rate"
    doc = one_run(4200004211, 1, seconds=10)
    assert doc["correct"] is True
    metrics = doc["metrics"]
    assert set(metrics) == set(entered)
    for name, m in entered.items():
        assert metrics[name]["unit"] == m["unit"], name
    assert 0 < metrics["peer_served_share.fleet"]["value"] < 100
    for name in FLEET[1:]:
        assert metrics[name]["value"] >= 0, name
    assert metrics["serve_write_s.fleet"]["value"] > 0


def test_control_is_not_correct():
    doc = one_run(4200004212, 0, "--control")
    assert doc["correct"] is False and doc["failed"] == 0
    for number in ("host_verify_batches", "device_bytes_short"):
        assert doc["checks"][number]["value"] > 0
    for number in ("unanswered", "wrong_answers", "agent-1_pieces_short",
                   "agent-2_pieces_short"):
        assert doc["checks"][number]["value"] == 0


def _scrape(up: float, stages: dict | None) -> str:
    text = f"p2p_piece_bytes_up_total {up}\n"
    for stage, s in (stages or {}).items():
        text += f'p2p_serve_stage_seconds_sum{{stage="{stage}"}} {s}\n'
    return text


def test_fleet_reader_sums_over_roles_and_reads_zero_without_the_family():
    # Bytes up over the window: origin 8 MiB, the agents 4 and 4 MiB.
    mib = 1 << 20
    ups = {"origin": 8 * mib, "agent": 4 * mib, "agent-1": 4 * mib, "agent-2": 0}
    parent = {"prom": {"tracker": {"before": "", "after": ""}, **{
        role: {"before": _scrape(0, None), "after": _scrape(up, None)}
        for role, up in ups.items()}}}
    assert read_metric("peer_served_share.fleet", parent) == 50.0
    for name in FLEET[1:]:
        assert read_metric(name, parent) == 0.0, name
    child = {"prom": {role: {"before": _scrape(0, {"write": 1.0}),
                             "after": _scrape(up, {"write": 1.0 + up / mib / 4})}
                      for role, up in ups.items()}}
    # A quarter of a second a MiB served: one second a 4 MiB piece.
    assert read_metric("serve_write_s.fleet", child) == 1.0
    nothing = {"prom": {role: {"before": "", "after": ""} for role in ups}}
    assert read_metric("serve_admit_s.fleet", nothing) is None
