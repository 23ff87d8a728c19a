"""What decides ``correct`` has been shown to fail: whole runs of run.py on
the CPU at the rehearsal's sizes (the look for a chip skipped), once sound,
once as the control (the chip's component hashing on the host), and once
for each fault a cell can have: an answer altered where it is produced.

    python3 -m pytest benchmark/tests -q        (about three minutes)

The pull cases run the configuration ``agent-tpu`` (in BENCHMARK.json since
PR 35) under the small mix, a cell BENCHMARK.json does not hold, made here
the way a later PR would make it: one entry in ``workloads`` of a copy of
BENCHMARK.json, and its name in the ``workloads`` lists of the metrics it
reports; no file under ``benchmark/`` is edited. (The committed pull cell's
own rehearsal is ``test_pull_cell.py``.)

The benchmark's own runs do not run these.
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

PUSH = "origin-tpu.push-small"
PULL = "agent-tpu.pull-small"


@pytest.fixture(scope="module")
def with_pull_cell(tmp_path_factory):
    """BENCHMARK.json plus one cell of the configuration ``agent-tpu``,
    named in the lists of the pull side's metrics."""
    bench = contract.load_benchmark()
    bench["workloads"].append({
        "name": PULL, "config": "agent-tpu", "traffic": "small-1k-1m", "chips": 1,
        "why": "3 closed-loop pullers of small blobs through the agent"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("pull_rate", "pull_p90", "verify_rows_mean"):
            m["workloads"].append(PULL)
    assert contract.check_benchmark(bench) == []
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def one_run(cell, seed, *extra, **kwargs):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", cell, "--seed", str(seed), "--seconds", "6",
             "--trace", "0", "--scale", "tiny", *extra],
            require_chip=False, **kwargs,
        )
    assert rc == 0, "the run printed no result"
    return json.loads(out.getvalue().strip().splitlines()[-1])


def bench_of(cell, with_pull_cell):
    return with_pull_cell if cell == PULL else None


@pytest.mark.parametrize("cell", [PUSH, PULL])
def test_sound_run_is_correct(cell, with_pull_cell):
    doc = one_run(cell, 2147483700, bench_path=bench_of(cell, with_pull_cell))
    assert doc["correct"] is True and doc["failed"] == 0 and doc["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in doc["checks"].values())
    assert set(doc["metrics"]) == (
        {"push_p90", "setup_s"} if cell == PUSH else {"pull_rate", "setup_s"})


@pytest.mark.parametrize("cell", [PUSH, PULL])
def test_control_host_hasher_is_not_correct(cell, with_pull_cell):
    doc = one_run(cell, 2147483701, "--control",
                  bench_path=bench_of(cell, with_pull_cell))
    assert doc["correct"] is False
    # Every answer is right; only the device guarantee fails, on the
    # device's counters and on the host hasher's.
    assert doc["failed"] == 0
    numbers = {PUSH: ("device_bytes_short", "host_hasher_bytes", "host_hasher_pieces"),
               PULL: ("device_bytes_short", "host_verify_batches")}[cell]
    for number in numbers:
        assert doc["checks"][number]["value"] > 0


@pytest.mark.parametrize("cell,fault", [
    (PUSH, "piece_hash"),
    (PULL, "delivered_byte"),
])
def test_altered_answer_is_not_correct(cell, fault, with_pull_cell, monkeypatch):
    monkeypatch.setenv("BENCH_FAULT", fault)
    doc = one_run(cell, 2147483702, launcher=os.path.join(HERE, "faulty_serve.py"),
                  bench_path=bench_of(cell, with_pull_cell))
    assert doc["correct"] is False
    assert doc["checks"]["wrong_answers"]["value"] > 0
