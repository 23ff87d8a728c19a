"""A herd of several agents, and the rollout drive.

Part one pins the four committed configurations: with the ports fixed, the
herd spawns the same processes in the same order, each with the same argv,
on the chip or pinned to the CPU as before, and writes the same YAML over
the shipped files, as the herd did before it knew of fleets. That herd's
output is recorded in ``herd_pins.json`` (paths as ``{repo}`` and
``{work}``). No process is started.

Part two rehearses a fleet on the CPU: ``agent-tpu-fleet.json`` (three
agents: the chip's through ``serve.py``, two peers on ``hasher: cpu``, each
with a per-host egress cap from ``settings``) in a rollout of the pull
traffic's ``tiny`` deck, through a copy of BENCHMARK.json with one cell more
(``bench_path``), the look for a chip skipped. The sound run is correct,
every agent's pulls are compared, and the agents serve each other piece
bytes; the control (the chip's agent verifying on the host) is not correct.

    python3 -m pytest benchmark/tests/test_fleet_herd.py -q   (about a minute)

The benchmark's own runs do not run these.
"""

import io
import json
import os
import sys
from collections import Counter
from contextlib import redirect_stdout

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import contract  # noqa: E402
import herd as herd_mod  # noqa: E402
import run  # noqa: E402

CELL = "agent-tpu-fleet.pull-fleet"
CONFIGS = ("origin-tpu", "origin-tpu-layers", "agent-tpu", "origin-tpu-sharded")


class _Spawned:
    """Stands in for ``herd.Child``: keeps what would have been started."""

    def __init__(self, name, argv, log_dir, chip):
        self.name, self.argv, self.chip = name, list(argv), chip

    def wait_ready(self, timeout):
        return {}

    def died_of_taken_address(self):
        return False

    def stop(self):
        pass


class _NoControl:
    def __init__(self, port):
        pass

    def close(self):
        pass


def herd_as_built(module, config: dict, root: str) -> dict:
    """What ``module``'s herd spawns for ``config`` and the YAML it writes,
    ports numbered in the order of their names from 21000."""
    saved = module.Child, module.Control
    module.Child, module.Control = _Spawned, _NoControl
    try:
        h = module.Herd(config, os.path.join(root, "work"), os.path.join(root, "logs"))
        h.ports = {name: 21000 + i for i, name in enumerate(sorted(h.ports))}
        h.start(timeout=1)
        yaml = {}
        for name in sorted(os.listdir(h.work)):
            if name.endswith(".yaml"):
                with open(os.path.join(h.work, name)) as f:
                    yaml[name] = f.read()
        built = {"ports": sorted(h.ports),
                 "spawned": [[c.name, c.chip, c.argv] for c in h.children.values()],
                 "yaml": yaml}
        work = h.work
        h.stop()
    finally:
        module.Child, module.Control = saved
    text = json.dumps(built).replace(work, "{work}").replace(module.REPO, "{repo}")
    return json.loads(text)


def config_of(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_committed_configs_build_the_herd_they_built(name, tmp_path):
    with open(os.path.join(HERE, "herd_pins.json")) as f:
        pinned = json.load(f)[name]
    assert herd_as_built(herd_mod, config_of(name), str(tmp_path)) == pinned


def test_fleet_config_builds_one_process_an_agent(tmp_path):
    with open(os.path.join(HERE, "agent-tpu-fleet.json")) as f:
        config = json.load(f)
    config["agents"] = 4
    built = herd_as_built(herd_mod, config, str(tmp_path))
    assert [name for name, _, _ in built["spawned"]] == [
        "tracker", "origin", "agent", "agent-1", "agent-2", "agent-3"]
    assert [name for name, chip, _ in built["spawned"] if chip] == ["agent"]
    ports = set()
    for name, _, argv in built["spawned"][2:]:
        assert argv[argv.index("--store") + 1] == "{work}/" + name + "-store"
        assert argv[argv.index("--hasher") + 1] == ("tpu" if name == "agent" else "cpu")
        assert argv[argv.index("--config") + 1] == "{work}/" + name + ".yaml"
        ports |= {argv[argv.index("--port") + 1], argv[argv.index("--p2p-port") + 1]}
        tracker = argv[argv.index("--tracker") + 1]
        assert built["yaml"][name + ".yaml"] == (
            "extends: {repo}/config/agent/base.yaml\n"
            'p2p_bandwidth: {"egress_bps": 300000000, "ingress_bps": 0}\n')
    assert len(ports) == 8
    assert tracker == "127.0.0.1:" + str(21000 + built["ports"].index("tracker"))
    # The peers are plain CLI agents; only the chip's goes through serve.py.
    assert all(argv[:3] == ["-m", "kraken_tpu.cli", "agent"]
               for name, _, argv in built["spawned"][3:])


@pytest.fixture(scope="module")
def with_fleet_cell(tmp_path_factory):
    """BENCHMARK.json plus the test-only configuration and one cell of it."""
    bench = contract.load_benchmark()
    bench["configs"].append({
        "name": "agent-tpu-fleet",
        "source": "https://github.com/uber/kraken README, benchmark paragraph",
        "file": "benchmark/tests/agent-tpu-fleet.json",
        "reduced": ["origins", "trackers", "agents"],
        "why": "three agents that serve each other, the first on the chip"})
    bench["workloads"].append({
        "name": CELL, "config": "agent-tpu-fleet", "traffic": "pull-layers-100m-1g",
        "chips": 1, "why": "rollouts of 100 MB-1 GB layers through three agents at once"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("pull_rate", "pull_p90", "verify_rows_mean"):
            m["workloads"].append(CELL)
    assert contract.check_benchmark(bench) == []
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


def one_run(seed, bench_path, *extra):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(
            ["--workload", CELL, "--seed", str(seed), "--seconds", "8",
             "--trace", "0", "--scale", "tiny", *extra],
            require_chip=False, bench_path=bench_path,
        )
    assert rc == 0, "the run printed no result"
    return json.loads(out.getvalue().strip().splitlines()[-1])


def said(err: str, event: str) -> dict:
    lines = [json.loads(line) for line in err.splitlines()
             if line.startswith('{"event": "%s"' % event)]
    assert len(lines) == 1, event
    return lines[0]


def test_rollout_sound_run_is_correct_and_agents_serve_each_other(with_fleet_cell, capsys):
    doc = one_run(2147483760, with_fleet_cell)
    assert doc["correct"] is True and doc["failed"] == 0 and doc["attempted"] > 0
    assert set(doc["checks"]) == {
        "unanswered", "wrong_answers", "host_verify_batches", "device_pieces_short",
        "device_bytes_short", "agent-1_pieces_short", "agent-2_pieces_short"}
    assert all(c["value"] == 0 for c in doc["checks"].values())
    assert set(doc["metrics"]) == {"pull_rate", "setup_s"}
    # Every rollout pulled its blob through every agent, and each pull was
    # compared: the same number of records an agent, warm-up's and window's.
    with open(os.path.join(BENCH, "_out", CELL, "records.jsonl")) as f:
        records = [json.loads(line) for line in f]
    for phase in ("warmup", "window"):
        per_agent = Counter(r["agent"] for r in records if r["phase"] == phase)
        assert set(per_agent) == {"agent", "agent-1", "agent-2"}, phase
        assert len(set(per_agent.values())) == 1, per_agent
    assert all(r["ok"] for r in records)
    assert doc["attempted"] == sum(1 for r in records if r["phase"] == "window")
    fleet = said(capsys.readouterr().err, "fleet")
    roles = fleet["roles"]
    assert set(roles) == {"tracker", "origin", "agent", "agent-1", "agent-2"}
    assert all(row["cpu_s"] > 0 for row in roles.values())
    assert sum(roles[a]["piece_bytes_up"] for a in ("agent", "agent-1", "agent-2")) > 0
    assert 0 < fleet["agents_served_share"] < 1
    assert all(roles[a]["pulls"] > 0 for a in ("agent", "agent-1", "agent-2"))


def test_rollout_control_is_not_correct(with_fleet_cell):
    doc = one_run(2147483761, with_fleet_cell, "--control")
    assert doc["correct"] is False and doc["failed"] == 0
    for number in ("host_verify_batches", "device_bytes_short"):
        assert doc["checks"][number]["value"] > 0
    for number in ("unanswered", "wrong_answers", "agent-1_pieces_short"):
        assert doc["checks"][number]["value"] == 0
