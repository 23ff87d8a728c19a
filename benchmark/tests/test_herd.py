"""``herd.free_port`` closes the socket it chose a port with, so a port can
be taken before the child binds it (one run of thirteen died at boot for it,
PR 25). A child that exits at boot on an address in use makes the herd draw
new ports and boot once more, and say so; anything else fails as it did.

    python3 -m pytest benchmark/tests/test_herd.py -q   (about a minute)
"""

import json
import os
import socket
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import herd as herd_mod  # noqa: E402


@pytest.fixture
def config():
    with open(os.path.join(BENCH, "configs", "origin-tpu.json")) as f:
        return json.load(f)


def boot(config, tmp_path, squat_on, monkeypatch):
    """A herd on the CPU whose ``squat_on`` port is taken before its boot."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    h = herd_mod.Herd(config, str(tmp_path / "work"), str(tmp_path / "logs"),
                      chip_hasher="cpu")
    first = dict(h.ports)
    squatter = socket.create_server(("127.0.0.1", h.ports[squat_on]))
    try:
        h.start(timeout=120)
        return h, first
    except BaseException:
        h.stop()
        raise
    finally:
        squatter.close()


@pytest.mark.parametrize("squat_on", ["tracker", "control"])
def test_taken_port_boots_once_more_and_says_so(config, tmp_path, squat_on,
                                                monkeypatch, capfd):
    h, first = boot(config, tmp_path, squat_on, monkeypatch)
    try:
        assert h.ports[squat_on] != first[squat_on]
        assert set(h.ready) == {"tracker", "origin", "agent"}
        assert h.control.ask(op="describe")["count"] >= 1
    finally:
        h.stop()
    said = [json.loads(line) for line in capfd.readouterr().err.splitlines()
            if line.startswith('{"event": "herd_reboot"')]
    assert len(said) == 1 and said[0]["ports"] == first
    assert said[0]["address_in_use"] == [
        {"tracker": "tracker", "control": "origin"}[squat_on]]
    assert any(name.endswith(".boot1") for name in os.listdir(tmp_path / "logs"))


def test_taken_port_of_a_fleets_agent_boots_once_more(tmp_path, monkeypatch, capfd):
    """The ports of ``agent-1`` ... are drawn anew too."""
    with open(os.path.join(HERE, "agent-tpu-fleet.json")) as f:
        fleet = json.load(f)
    h, first = boot(fleet, tmp_path, "agent-2_p2p", monkeypatch)
    try:
        assert h.ports["agent-2_p2p"] != first["agent-2_p2p"]
        assert set(h.ready) == {"tracker", "origin", "agent", "agent-1", "agent-2"}
    finally:
        h.stop()
    said = [json.loads(line) for line in capfd.readouterr().err.splitlines()
            if line.startswith('{"event": "herd_reboot"')]
    assert len(said) == 1 and said[0]["address_in_use"] == ["agent-2"]


def test_other_boot_failures_are_not_retried(config, tmp_path, monkeypatch, capfd):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    h = herd_mod.Herd(config, str(tmp_path / "work"), str(tmp_path / "logs"),
                      chip_hasher="no-such-hasher")
    try:
        with pytest.raises(herd_mod.HerdError):
            h.start(timeout=120)
    finally:
        h.stop()
    assert "herd_reboot" not in capfd.readouterr().err
