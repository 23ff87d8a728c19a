"""The layer cell's rehearsal: whole runs of run.py on the CPU at the
traffic file's ``tiny`` deck (four blobs of 4.5-14 MB: one to three whole
4 MiB pieces and a tail each, through the shipped 64 MiB window), the look
for a chip skipped. A sound run prints a line that meets the contract, a
traced one prints every per-layer metric BENCHMARK.json enters for the cell
(the fifteen it shares with ``origin-tpu.push-small`` and its own three),
and the control (the origin hashing on the host) comes out not correct
with every answer right.

    python3 -m pytest benchmark/tests/test_layers_cell.py -q   (about a minute and a half)

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

CELL = "origin-tpu-layers.push-layers"
OWN = ("push_rate", "ingest_read_s", "origin_cpu_per_push_s")


def one_run(seed, trace, *extra):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "6",
             "--trace", str(trace), "--scale", "tiny", *extra],
            require_chip=False,
        )
    assert rc == 0, "the run printed no result"
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_prints_a_contract_clean_line():
    doc = one_run(2147483720, 0)
    assert doc["correct"] is True and doc["failed"] == 0 and doc["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in doc["checks"].values())
    assert set(doc["metrics"]) == {"push_p90", "setup_s"}


def test_traced_rehearsal_prints_all_eighteen_per_layer_metrics():
    bench = contract.load_benchmark()
    entered = contract.metrics_of(bench, CELL, True)
    assert len(entered) == 18 and set(OWN) <= {m["name"] for m in entered}
    for m in entered:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL], m["name"]
    doc = one_run(2147483721, 1)
    assert doc["correct"] is True
    metrics = doc["metrics"]
    assert set(metrics) == {m["name"] for m in entered}
    for m in entered:
        assert metrics[m["name"]]["unit"] == m["unit"], m["name"]
    assert metrics["push_rate"]["value"] > 0
    # A window waits for its bytes at least as long as it takes to copy
    # them in; the origin burns CPU on every push.
    assert metrics["ingest_read_s"]["value"] > 0
    assert metrics["origin_cpu_per_push_s"]["value"] > 0
    # More than one piece a section: the uniform kernel's side of the hasher
    # ran (a small push is one row a section, piece_rows_mean.push 1.0).
    assert metrics["piece_rows_mean.push"]["value"] > 1


def test_control_host_hasher_is_not_correct():
    doc = one_run(2147483722, 0, "--control")
    assert doc["correct"] is False and doc["failed"] == 0
    for number in ("device_bytes_short", "host_hasher_bytes", "host_hasher_pieces"):
        assert doc["checks"][number]["value"] > 0
