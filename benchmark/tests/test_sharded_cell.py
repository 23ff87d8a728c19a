"""The sharded cell's rehearsal: whole runs of run.py on the CPU at the
traffic file's ``tiny`` deck (four blobs of 4.5-14 MB), the look for a chip
skipped, with ``JAX_NUM_CPU_DEVICES=4`` in the environment so that the chip's
child (the origin, ``--hasher tpu-sharded``) builds its mesh over four
virtual CPU devices (``herd.py`` ``child_env`` strips only the ``XLA_FLAGS``
form). A sound run prints a line that meets the contract, a traced one prints
the twenty-one per-layer metrics BENCHMARK.json enters for the cell (the
seventeen it shares with ``origin-tpu-layers.push-layers`` and its own four),
the control (the origin hashing on the host) comes out not correct with every
answer right, and a run whose sharded hasher alters its last digest reads
``wrong_answers`` on every push.

The traced run goes through ``one_piece_window_serve.py`` (a 4 MiB ingest
window), because the tiny deck holds no whole 64 MiB window and only a window
of whole pieces takes the ``transfer`` stage that ``ingest_transfer_s`` reads.

    python3 -m pytest benchmark/tests/test_sharded_cell.py -q   (about three minutes)

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

CELL = "origin-tpu-sharded.push-layers"
OWN = ("ingest_transfer_s", "mesh_held_s.push", "mesh_rows_mean.push",
       "mesh_hbm_roofline.push")


@pytest.fixture(autouse=True)
def four_virtual_devices(monkeypatch):
    monkeypatch.setenv("JAX_NUM_CPU_DEVICES", "4")


def one_run(seed, trace, *extra, **kwargs):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "6",
             "--trace", str(trace), "--scale", "tiny", *extra],
            require_chip=False, **kwargs,
        )
    assert rc == 0, "the run printed no result"
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_prints_a_contract_clean_line():
    bench = contract.load_benchmark()
    assert contract.check_benchmark(bench) == []
    assert contract.cell_of(bench, CELL)["chips"] == 4
    doc = one_run(2147483760, 0)
    assert doc["correct"] is True and doc["failed"] == 0 and doc["attempted"] > 0
    assert all(c["value"] == 0 and c["limit"] == 0 for c in doc["checks"].values())
    assert set(doc["metrics"]) == {"push_p90", "setup_s"}
    assert doc["device"]["count"] == 4


def test_traced_rehearsal_prints_all_twenty_one_per_layer_metrics():
    bench = contract.load_benchmark()
    entered = contract.metrics_of(bench, CELL, True)
    names = {m["name"] for m in entered}
    assert len(entered) == 21 and set(OWN) <= names
    assert "hash_hbm_roofline.push" not in names  # one chip's peak: four times too high
    for m in entered:
        if m["name"] in OWN:
            assert m["workloads"] == [CELL] and m["moves"] == "push_p90", m["name"]
    doc = one_run(2147483761, 1,
                  launcher=os.path.join(HERE, "one_piece_window_serve.py"))
    assert doc["correct"] is True
    metrics = doc["metrics"]
    assert set(metrics) == names
    for m in entered:
        assert metrics[m["name"]]["unit"] == m["unit"], m["name"]
    assert metrics["ingest_transfer_s"]["value"] > 0
    assert metrics["mesh_held_s.push"]["value"] > 0
    # Every sharded dispatch is padded to the mesh's four devices: a window
    # of one piece is four rows, a last window of one to three whole pieces too.
    assert metrics["mesh_rows_mean.push"]["value"] == 4


def test_control_host_hasher_is_not_correct():
    doc = one_run(2147483762, 0, "--control")
    assert doc["correct"] is False and doc["failed"] == 0
    for number in ("unanswered", "wrong_answers", "readback_mismatches"):
        assert doc["checks"][number]["value"] == 0
    for number in ("device_bytes_short", "device_pieces_short",
                   "host_hasher_bytes", "host_hasher_pieces"):
        assert doc["checks"][number]["value"] > 0


def test_altered_sharded_digest_is_a_wrong_answer_on_every_push():
    doc = one_run(2147483763, 0,
                  launcher=os.path.join(HERE, "faulty_sharded_serve.py"))
    assert doc["correct"] is False
    # Warm-up's answers are held to the reference too: one deal of four.
    assert doc["checks"]["wrong_answers"]["value"] == doc["attempted"] + 4
    assert doc["failed"] == doc["attempted"]
    # The mesh did its work; the answer was altered after it.
    for number in ("device_bytes_short", "device_pieces_short", "host_hasher_bytes"):
        assert doc["checks"][number]["value"] == 0
