"""The processes of one run: a tracker, an origin and one or more agents
through the CLI an operator calls, with the shipped ``config/*/base.yaml``
(only store paths, ports, the file backend's root and a role's own
``settings`` are written over it). The component the configuration gives
the chip goes through ``serve.py``; every other child is pinned to the CPU.
This parent never imports JAX.

What a configuration states of its herd:

- ``herd``: a spec for ``tracker``, ``origin`` and ``agent``. A spec gives
  the ``hasher`` and the shipped ``config``; ``override: "backend_root"``
  points the file backend into the run's work directory, and ``settings``
  is a mapping of top-level keys laid over the shipped file (a per-host cap:
  ``{"p2p_bandwidth": {"egress_bps": ..., "ingress_bps": ...}}``). A role
  with either gets a generated file that ``extends:`` the shipped one; a
  role with neither gets the shipped path itself.
- ``agents``: how many agents (1 when absent). The first is the role
  ``agent``; the others are ``agent-1`` ... ``agent-{N-1}``, each with ports,
  store and log of its own and the same ``--tracker``. They take the agent's
  spec, with the hasher ``peer_hasher`` where the spec gives one, and run on
  the CPU whatever ``chip`` says: one process holds the chip.
- ``chip``: the role that goes through ``serve.py`` (``origin`` or
  ``agent``).

Child handling is copied from ``chip_smoke.py`` (PR 21), which later PRs may
change; the yardstick may not move with it.
"""

from __future__ import annotations

import json
import os
import queue
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class HerdError(Exception):
    pass


def free_port() -> int:
    """A port that was free when asked. The socket is closed before the child
    binds it, so anything that connects out in between (the tracker is up and
    dialling by then) can be handed the same number: ``Herd.start`` boots once
    more on new ports when a child dies of that."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


ADDRESS_IN_USE = re.compile(r"address already in use|Errno 98\b", re.IGNORECASE)


def child_env(chip: bool) -> dict:
    """The chip's child gets the caller's environment less any
    virtual-device flag; every other child is pinned to the CPU."""
    env = dict(os.environ, PYTHONPATH=REPO)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.pop("XLA_FLAGS", ""),
    ).strip()
    if flags:
        env["XLA_FLAGS"] = flags
    if not chip:
        env["JAX_PLATFORMS"] = "cpu"
    else:
        # Keep every program the chip's child compiles, also the scans that
        # compile in under JAX's one-second threshold, so that only the first
        # run in a checkout compiles them.
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return env


class Child:
    """stdout is pumped for the READY line and kept draining; stderr, the
    program's JSON log, goes to a file."""

    def __init__(self, name: str, argv: list[str], log_dir: str, chip: bool):
        self.name = name
        self.log_path = os.path.join(log_dir, name + ".log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, *argv], stdout=subprocess.PIPE, stderr=self._log,
            env=child_env(chip), cwd=REPO,
        )
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for raw in self.proc.stdout:
            self._lines.put(raw.decode(errors="replace").rstrip("\n"))
        self._lines.put(None)

    def wait_ready(self, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self._lines.get(timeout=max(0.1, deadline - time.monotonic()))
            except queue.Empty:
                raise HerdError(
                    f"{self.name}: no READY line in {timeout:.0f} s; log tail: "
                    + self.log_tail()
                ) from None
            if line is None:
                raise HerdError(
                    f"{self.name} exited rc={self.proc.wait()}; log tail: "
                    + self.log_tail()
                )
            if line.startswith("READY "):
                return json.loads(line[len("READY "):])

    def log_tail(self, nbytes: int = 1500) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(max(0, os.path.getsize(self.log_path) - nbytes))
            return f.read().decode(errors="replace")

    def died_of_taken_address(self) -> bool:
        """The child has exited and its log says that it could not bind."""
        return self.proc.poll() is not None and bool(
            ADDRESS_IN_USE.search(self.log_tail(4000)))

    def stop(self) -> None:
        """SIGINT is the CLI's immediate stop; the process is gone, and the
        chip free, when this returns."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


class Control:
    """The parent's end of ``serve.py``'s control socket."""

    def __init__(self, port: int, timeout: float = 240):
        deadline = time.monotonic() + 30
        while True:
            try:
                self._sock = socket.create_connection(("127.0.0.1", port), 5)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise HerdError("the chip's child opened no control socket")
                time.sleep(0.1)
        self._sock.settimeout(timeout)
        self._file = self._sock.makefile("rw")
        self._lock = threading.Lock()

    def ask(self, **req) -> dict:
        with self._lock:
            self._file.write(json.dumps(req) + "\n")
            self._file.flush()
            line = self._file.readline()
        if not line:
            raise HerdError(f"control socket closed on {req}")
        out = json.loads(line)
        if "error" in out:
            raise HerdError(f"control {req.get('op')}: {out['error']}")
        return out

    def close(self) -> None:
        self._sock.close()


class Herd:
    def __init__(self, config: dict, work: str, logs: str,
                 chip_hasher: str | None = None, launcher: str | None = None):
        """``chip_hasher`` replaces the hasher of the chip's component (the
        control runs it as ``cpu``); ``launcher`` replaces ``serve.py`` (the
        fault test breaks the served path in one of its own)."""
        self.launcher = launcher or os.path.join(HERE, "serve.py")
        self.config = config
        self.work = work
        self.logs = logs
        self.chip_role = config["chip"]
        self.agents = ["agent"] + [f"agent-{i}" for i in range(1, int(config.get("agents", 1)))]
        self.nodes = ["origin", *self.agents]  # every role but the tracker
        self.hashers = {
            role: spec.get("hasher") for role, spec in config["herd"].items()
        }
        peer_hasher = config["herd"]["agent"].get("peer_hasher", self.hashers["agent"])
        for role in self.agents[1:]:
            self.hashers[role] = peer_hasher
        if chip_hasher is not None:
            self.hashers[self.chip_role] = chip_hasher
        shutil.rmtree(work, ignore_errors=True)
        shutil.rmtree(logs, ignore_errors=True)
        os.makedirs(work)
        os.makedirs(logs)
        self.backend_root = os.path.join(work, "backend")
        self.ports = self._draw_ports()
        self.children: dict[str, Child] = {}
        self.ready: dict[str, dict] = {}
        self.control: Control | None = None

    def _draw_ports(self) -> dict[str, int]:
        """A port for the tracker, two for every other role (its API and its
        P2P listener, ``<role>_p2p``) and one for the control socket."""
        names = ["tracker", *(n for role in self.nodes for n in (role, role + "_p2p")),
                 "control"]
        return {name: free_port() for name in names}

    def addr(self, role: str) -> str:
        return f"127.0.0.1:{self.ports[role]}"

    def _spec(self, role: str) -> dict:
        return self.config["herd"]["agent" if role in self.agents else role]

    def _config_path(self, role: str) -> str:
        spec = self._spec(role)
        shipped = os.path.join(REPO, spec["config"])
        backend = spec.get("override") == "backend_root"
        settings = spec.get("settings") or {}
        if not backend and not settings:
            return shipped
        text = f"extends: {shipped}\n"
        if backend:
            text += (
                "backends:\n"
                '  - namespace: ".*"\n'
                "    backend: file\n"
                f"    config: {{root: {self.backend_root}}}\n"
            )
        for key, value in settings.items():  # JSON is YAML's flow style
            text += f"{key}: {json.dumps(value)}\n"
        path = os.path.join(self.work, role + ".yaml")
        with open(path, "w") as f:
            f.write(text)
        return path

    def _argv(self, role: str) -> list[str]:
        if role == "tracker":
            return [
                "tracker", "--host", "127.0.0.1",
                "--config", os.path.join(REPO, "config/tracker/base.yaml"),
                "--port", str(self.ports["tracker"]),
                "--origins", self.addr("origin"),
            ]
        component = "agent" if role in self.agents else role
        return [
            component, "--host", "127.0.0.1", "--config", self._config_path(role),
            "--store", os.path.join(self.work, role + "-store"),
            "--port", str(self.ports[role]),
            "--p2p-port", str(self.ports[role + "_p2p"]),
            "--tracker", self.addr("tracker"),
            "--hasher", self.hashers[role],
        ]

    def spawn(self, role: str) -> None:
        chip = role == self.chip_role
        if chip:
            argv = [self.launcher, str(self.ports["control"]),
                    *self._argv(role)]
        else:
            argv = ["-m", "kraken_tpu.cli", *self._argv(role)]
        self.children[role] = Child(role, argv, self.logs, chip)

    def start(self, timeout: float) -> None:
        """Boot the herd. When a child exits at boot because a port it was
        given had been taken since ``free_port`` chose it, draw new ports and
        boot once more, and say so on standard error (the run's log). Any
        other failure, and a second one of this kind, is the run's."""
        try:
            self._boot(timeout)
        except HerdError as e:
            taken = sorted(name for name, child in self.children.items()
                           if child.died_of_taken_address())
            if not taken:
                raise
            print(json.dumps({"event": "herd_reboot", "address_in_use": taken,
                              "ports": self.ports, "error": str(e)[:400]}),
                  file=sys.stderr, flush=True)
            self._stop_children()
            for name in os.listdir(self.logs):  # the first boot's logs stay beside
                os.replace(os.path.join(self.logs, name),
                           os.path.join(self.logs, name + ".boot1"))
            shutil.rmtree(self.work, ignore_errors=True)
            os.makedirs(self.work)
            self.ports = self._draw_ports()
            self._boot(timeout)

    def _boot(self, timeout: float) -> None:
        """Tracker first (the others announce to it), then the origin and
        every agent side by side."""
        self.spawn("tracker")
        self.ready["tracker"] = self.children["tracker"].wait_ready(timeout)
        for role in self.nodes:
            self.spawn(role)
        for role in self.nodes:
            self.ready[role] = self.children[role].wait_ready(timeout)
        self.control = Control(self.ports["control"])

    def _stop_children(self) -> None:
        if self.control is not None:
            self.control.close()
            self.control = None
        for child in self.children.values():
            child.stop()
        self.children.clear()

    def stop(self) -> None:
        self._stop_children()
        shutil.rmtree(self.work, ignore_errors=True)
