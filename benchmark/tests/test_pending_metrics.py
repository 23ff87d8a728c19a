"""The seven per-layer metrics of ``pending_per_layer.json`` (PR 25), entered
the way a later ``benchmark`` PR will enter them: appended to a copy of
BENCHMARK.json, naming files already under ``benchmark/`` and editing none.
One traced run of run.py on the CPU at the rehearsal's sizes has to print
all seven beside the accepted ones.

    python3 -m pytest benchmark/tests/test_pending_metrics.py -q   (about a minute)

The benchmark's own runs do not run this.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import contract  # noqa: E402
import run  # noqa: E402

CELL = "origin-tpu.push-small"


@pytest.fixture(scope="module")
def pending():
    with open(os.path.join(os.path.dirname(HERE), "pending_per_layer.json")) as f:
        return json.load(f)["per_layer"]


@pytest.fixture(scope="module")
def with_pending(pending, tmp_path_factory):
    bench = contract.load_benchmark()
    bench["per_layer"] += pending
    assert contract.check_benchmark(bench) == []
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def test_traced_rehearsal_prints_the_pending_metrics(pending, with_pending):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", CELL, "--seed", "2147483710", "--seconds", "6",
             "--trace", "1", "--scale", "tiny"],
            require_chip=False, bench_path=with_pending,
        )
    assert rc == 0, "the run printed no result"
    doc = json.loads(out.getvalue().strip().splitlines()[-1])
    assert doc["correct"] is True
    metrics = doc["metrics"]
    for entry in pending:
        assert metrics[entry["name"]]["unit"] == entry["unit"], entry["name"]
    for name in ("piece_device_share.push", "scan_useful_blocks.push",
                 "first_use_share.push"):
        assert 0 <= metrics[name]["value"] <= 100, name
    assert metrics["scan_rows_mean.push"]["value"] >= 1
    # What a commit waits for is the queue and the hash of its last window.
    parts = metrics["ingest_queue_s"]["value"] + metrics["ingest_hash_s"]["value"]
    assert parts <= metrics["commit_join_s"]["value"] * 1.05 + 0.05
