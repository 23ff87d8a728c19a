"""Every per-layer metric that BENCHMARK.json enters for the push cell
prints a number in a traced run: one whole run of run.py on the CPU at the
rehearsal's sizes, under BENCHMARK.json as it is committed. Seven of the
fifteen are PR 25's (ratios of the program's ``hasher_device_*`` and
``ingest_stage_seconds`` counters over the harness's two scrapes), entered
by PR 30; the two that name no kernel (``piece_useful_blocks.push``,
``piece_rows_mean.push``) read whatever ran the acknowledged path's pieces:
the ragged scan here, ``sha256_ragged_tiles`` on the chip.

    python3 -m pytest benchmark/tests/test_traced_metrics.py -q   (about a minute)

The traced stretch is the configuration's ``trace.stretch_s`` capped at half
the rehearsal's 6 s window. The benchmark's own runs do not run this.
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
PR25 = ("ingest_queue_s", "commit_join_s", "device_wait_s.push",
        "piece_device_share.push", "piece_useful_blocks.push",
        "piece_rows_mean.push", "first_use_share.push")


@pytest.fixture(scope="module")
def entered():
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    return contract.metrics_of(bench, CELL, True)


def test_the_pending_entries_are_entered(entered):
    names = [m["name"] for m in entered]
    assert len(names) >= 15 and set(PR25) <= set(names)  # later PRs add entries
    assert not os.path.exists(os.path.join(os.path.dirname(HERE), "pending_per_layer.json"))
    for name in ("piece_useful_blocks.push", "piece_rows_mean.push"):
        with open(os.path.join(os.path.dirname(HERE), "metrics", name + ".json")) as f:
            spec = json.load(f)
        for side in ("numerator", "denominator"):
            assert spec[side]["labels"] == {"purpose": "piece"}, (name, side)


def test_traced_rehearsal_prints_every_per_layer_metric(entered):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", CELL, "--seed", "2147483710", "--seconds", "6",
             "--trace", "1", "--scale", "tiny"],
            require_chip=False,
        )
    assert rc == 0, "the run printed no result"
    doc = json.loads(out.getvalue().strip().splitlines()[-1])
    assert doc["correct"] is True
    metrics = doc["metrics"]
    assert set(metrics) == {m["name"] for m in entered}
    for entry in entered:
        assert metrics[entry["name"]]["unit"] == entry["unit"], entry["name"]
    for name in ("piece_device_share.push", "piece_useful_blocks.push",
                 "first_use_share.push"):
        assert 0 <= metrics[name]["value"] <= 100, name
    assert metrics["piece_rows_mean.push"]["value"] >= 1
    # What a commit waits for is the queue and the hash of its last window.
    parts = metrics["ingest_queue_s"]["value"] + metrics["ingest_hash_s"]["value"]
    assert parts <= metrics["commit_join_s"]["value"] * 1.05 + 0.05
