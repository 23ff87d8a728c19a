"""The pull cell's rehearsal: whole runs of run.py on the CPU at the traffic
file's ``tiny`` deck (four blobs of 4.5-14 MB: one to three whole 4 MiB
pieces and a tail each, seeded through the CPU origin and pulled through
the agent by two clients), the look for a chip skipped. A sound run prints
a line that meets the contract, a traced one prints every per-layer metric
BENCHMARK.json enters for the cell, the control (the agent verifying on the
host) comes out not correct with every delivered byte right, and so does a
run whose agent alters one byte of every blob it delivers.

    python3 -m pytest benchmark/tests/test_pull_cell.py -q   (about two and a half minutes)

The benchmark's own runs do not run these.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import contract  # noqa: E402
import run  # noqa: E402

CELL = "agent-tpu.pull-layers"
SETUP = ("ready_s", "warm_s")  # shared with the push cells, move setup_s


def one_run(seed, trace, *extra, seconds=6, **kwargs):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace), "--scale", "tiny", *extra],
            require_chip=False, **kwargs,
        )
    assert rc == 0, "the run printed no result"
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_prints_a_contract_clean_line():
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    entered = {m["name"] for m in contract.metrics_of(bench, CELL, False)}
    assert entered == {"setup_s", "pull_rate"}
    doc = one_run(2147483740, 0)
    assert doc["correct"] is True and doc["failed"] == 0 and doc["attempted"] > 0
    assert set(doc["checks"]) == {"unanswered", "wrong_answers", "host_verify_batches",
                                  "device_pieces_short", "device_bytes_short"}
    assert all(c["value"] == 0 and c["limit"] == 0 for c in doc["checks"].values())
    assert set(doc["metrics"]) == entered
    assert doc["metrics"]["pull_rate"]["value"] > 0


def test_traced_rehearsal_prints_every_per_layer_metric():
    bench = contract.load_benchmark()
    entered = contract.metrics_of(bench, CELL, True)
    assert len(entered) >= 12  # later PRs add entries
    for m in entered:
        if m["name"] in SETUP:
            assert m["moves"] == "setup_s"
        else:  # the pull side's own: no push cell reads them
            assert m["workloads"] == [CELL] and m["moves"] == "pull_rate", m["name"]
    # Ten seconds, not six: the roofline's payload is the pulls that ended in
    # the window, and under the tracer the CPU backend's scan can take six
    # seconds over the rehearsal's first blob.
    doc = one_run(2147483741, 1, seconds=10)
    assert doc["correct"] is True
    metrics = doc["metrics"]
    assert set(metrics) == {m["name"] for m in entered}
    for m in entered:
        assert metrics[m["name"]]["unit"] == m["unit"], m["name"]
    for name in ("verify_useful_blocks.pull", "verify_device_share.pull",
                 "first_use_share.pull", "gen_busy.pull"):
        assert 0 <= metrics[name]["value"] <= 100, name
    # Verify is the only purpose that holds an agent's device hasher.
    assert metrics["verify_device_share.pull"]["value"] > 99
    assert metrics["verify_rows_mean"]["value"] >= 1
    assert metrics["pull_ops"]["value"] > 0 and metrics["pull_p90"]["value"] > 0
    assert metrics["agent_cpu_per_piece_s"]["value"] > 0
    # The idle gaps are named by what the clients were doing: pulling.
    assert {name for name, _ in doc["breakdown"]["idle_gaps"]} <= {"pull", "none"}


def test_control_host_verify_is_not_correct():
    doc = one_run(2147483742, 0, "--control")
    assert doc["correct"] is False
    # Every delivered byte is right; only the device guarantee fails.
    assert doc["failed"] == 0
    for number in ("unanswered", "wrong_answers"):
        assert doc["checks"][number]["value"] == 0
    # (verify_pieces_total counts on either path, so the pieces are covered.)
    for number in ("host_verify_batches", "device_bytes_short"):
        assert doc["checks"][number]["value"] > 0


def test_altered_delivered_byte_is_not_correct(monkeypatch):
    monkeypatch.setenv("BENCH_FAULT", "delivered_byte")
    doc = one_run(2147483743, 0, launcher=os.path.join(HERE, "faulty_serve.py"))
    assert doc["correct"] is False and doc["failed"] > 0
    assert doc["checks"]["wrong_answers"]["value"] > 0
    # The device did its work; the answer was altered after it.
    for number in ("host_verify_batches", "device_pieces_short", "device_bytes_short"):
        assert doc["checks"][number]["value"] == 0
