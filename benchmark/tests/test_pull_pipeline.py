"""The pull cell's request-slot and device-gap entries: the six stages of a
received piece (``piece_<stage>_s.pull``, over the agent's
``p2p_piece_stage_seconds``), the device's gap before a verify section
(``device_gap_s.pull``) and the part of it in which the verifier had no
piece (``verify_starved_s.pull``).

- the traced rehearsal prints all eight, none below 0, and the verify
  stage and the gap above 0;
- a program without the three families (an older agent, whose scrapes
  still carry every denominator) reads each of them as 0.0, not as
  nothing, so a traced run of it does not fail.

    python3 -m pytest benchmark/tests/test_pull_pipeline.py -q   (about three minutes)
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import contract  # noqa: E402
from readers import read_metric  # noqa: E402
from test_pull_cell import CELL, one_run  # noqa: E402

STAGES = ("wait", "recv", "queue", "verify", "write", "refill")
NEW = tuple(f"piece_{s}_s.pull" for s in STAGES) + (
    "device_gap_s.pull", "verify_starved_s.pull")


def test_entries_are_the_pull_sides_own():
    bench = contract.load_benchmark()
    entered = {m["name"]: m for m in contract.metrics_of(bench, CELL, True)}
    for name in NEW:
        m = entered[name]
        assert (m["source"], m["better"], m["unit"], m["moves"], m["workloads"]) == (
            "program_counter", "lower", "s", "pull_rate", [CELL]), name


def test_traced_rehearsal_prints_the_eight():
    doc = one_run(2147483751, 1, seconds=10)
    assert doc["correct"] is True
    metrics = {k: v["value"] for k, v in doc["metrics"].items()}
    for name in NEW:
        assert metrics[name] >= 0, name
    assert metrics["piece_verify_s.pull"] > 0
    assert metrics["device_gap_s.pull"] > 0
    # The starved part of a gap is inside the gap: a section ends on the
    # chip before the verifier hears of it.
    assert metrics["verify_starved_s.pull"] <= metrics["device_gap_s.pull"]


def _scrape(pieces: int, sections: int) -> str:
    """What an agent without the three families exports of the rest."""
    return "\n".join((
        f"verify_pieces_total {pieces}",
        f'hasher_device_sections_total{{kernel="sha256_tiles",purpose="verify"}} {sections}',
        f'hasher_device_held_seconds_total{{kernel="sha256_tiles",purpose="verify"}} {sections * 0.1}',
        f"process_cpu_seconds_total{{mode=\"user\"}} {pieces * 0.03}",
    )) + "\n"


def test_an_agent_without_the_families_reads_zero():
    ctx = {"prom": {"agent": {"before": _scrape(100, 10), "after": _scrape(3400, 240)}}}
    for name in NEW:
        assert read_metric(name, ctx) == 0.0, name
