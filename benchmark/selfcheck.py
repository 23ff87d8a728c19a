#!/usr/bin/env python3
"""The yardstick's own checks: seconds, on the CPU, no chip and no herd.

    python3 benchmark/selfcheck.py

- BENCHMARK.json keeps to the contract's keys, names, units and limits, and
  every cell's files resolve by name (configuration, traffic, one file a
  metric, a reader for every kind);
- the traffic generator gives every seed the same sizes in the same order,
  the same bytes for the same seed, and for another seed another head
  (``salt_bytes``) before the same body;
- the comparison that decides a pull's ``correct`` (``SeededBlob.differs_at``),
  fed a blob in pieces of any size, passes the blob's own bytes and sees one
  altered byte wherever it lies, a byte too many and a byte past the end;
- the trace reduction, on the recorded one-upload trace kept beside it,
  gives 0 < busy_s <= window_s and names ``sha256_tiles``;
- the validator that ``run.py`` holds its own last line to takes a sound
  line of each cell, traced and untraced, and refuses broken ones.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
os.environ["JAX_PLATFORMS"] = "cpu"

import contract  # noqa: E402
import traffic  # noqa: E402
from blobs import CHUNK, SeededBlob, piece_length_for  # noqa: E402

FAILED: list[str] = []


def check(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what)
    if not ok:
        FAILED.append(what)


def sample_line(bench: dict, cell: dict, trace: bool) -> dict:
    metrics = {
        m["name"]: {"value": 1.25, "unit": m["unit"]}
        for m in contract.metrics_of(bench, cell["name"], trace)
    }
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": cell["chips"],
              "memory_peak_bytes": 136314880}
    doc = {"correct": True, "attempted": 40, "failed": 0, "metrics": metrics,
           "device": device}
    if trace:
        device.update(busy_s=1.5, window_s=5.0)
        doc["breakdown"] = {"device_ops": [["jit_sha256_tiles", 1.2]],
                            "idle_gaps": [["patch", 0.4]]}
    doc["checks"] = {"wrong_answers": {"value": 0, "limit": 0}}
    return doc


def main() -> int:
    bench = contract.load_benchmark()
    problems = contract.check_benchmark(bench)
    check(not problems, f"BENCHMARK.json keeps to the contract {problems}")

    for cell in bench["workloads"]:
        with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
            config = json.load(f)
        mix = traffic.load_traffic(cell["traffic"])
        check(config["chip"] in config["herd"] and config["drive"]["op"] in ("push", "pull"),
              f"{cell['name']}: configuration names its chip's component and its drive")
        for m in contract.metrics_of(bench, cell["name"], True) + \
                contract.metrics_of(bench, cell["name"], False):
            with open(os.path.join(HERE, "metrics", m["name"] + ".json")) as f:
                kind = json.load(f)["kind"]
            importlib.import_module("readers." + kind)
        a = traffic.deal(mix)
        spec = mix["deck"]["log_uniform"]
        check(a == traffic.deal(mix) and sorted(a) == sorted(traffic.deck_sizes(mix))
              and len(a) == spec["count"]
              and all(spec["min_bytes"] <= x <= spec["max_bytes"] for x in a),
              f"{cell['name']}: every seed is dealt the same sizes in the same order")
        table = config["shipped"]["piece_lengths"]
        size = max(a)
        body, plen = mix["bytes"], piece_length_for(size, table)
        salt = body["salt_bytes"]
        one = SeededBlob(2147483659, 3, size, plen, body).chunk(0)
        again = SeededBlob(2147483659, 3, size, plen, body).chunk(0)
        other = SeededBlob(2147483660, 3, size, plen, body).chunk(0)
        next_blob = SeededBlob(2147483659, 4, size, plen, body).chunk(0)
        check(one == again and len(one) == min(size, 1 << 24)
              and one[:salt] != other[:salt] and one[salt:] == other[salt:]
              and one[salt:salt + 64] != next_blob[salt:salt + 64],
              f"{cell['name']}: same seed same bytes; other seed other head, same body; "
              "other blob other body")

        for trace in (False, True):
            doc = sample_line(bench, cell, trace)
            bad = contract.check_last_line(json.dumps(doc), bench, cell["name"],
                                           trace, cell["chips"])
            check(not bad, f"{cell['name']} trace={int(trace)}: a sound line passes {bad}")
            broken = []
            lacks = json.loads(json.dumps(doc))
            lacks["metrics"].pop(next(iter(lacks["metrics"])))
            broken.append(lacks)
            cpu = json.loads(json.dumps(doc))
            cpu["device"]["platform"] = "cpu"
            broken.append(cpu)
            nopeak = json.loads(json.dumps(doc))
            del nopeak["device"]["memory_peak_bytes"]
            broken.append(nopeak)
            if trace:
                over = json.loads(json.dumps(doc))
                over["device"]["busy_s"] = 6.0
                zero = json.loads(json.dumps(doc))
                zero["device"]["busy_s"] = 0.0
                broken += [over, zero]
            check(all(contract.check_last_line(json.dumps(b), bench, cell["name"],
                                               trace, cell["chips"]) for b in broken),
                  f"{cell['name']} trace={int(trace)}: {len(broken)} broken lines are refused")

    blob = SeededBlob(2147483659, 0, CHUNK + 300_001, 4 << 20, {"draw": 1, "salt_bytes": 64})
    whole = blob.chunk(0) + blob.chunk(1)

    def fed_in_pieces(data: bytes, step: int) -> bool:
        return any(blob.differs_at(off, data[off:off + step])
                   for off in range(0, len(data), step))

    check(len(whole) == blob.size
          and not any(fed_in_pieces(whole, step) for step in (65_536, 4 << 20, len(whole))),
          "differs_at passes the blob's own bytes, in pieces of 64 KiB, 4 MiB and whole")
    altered = []
    for at in (0, 63, CHUNK - 1, CHUNK, blob.size - 1):
        bad = bytearray(whole)
        bad[at] ^= 1
        altered.append(fed_in_pieces(bytes(bad), 65_536) and fed_in_pieces(bad, 4 << 20))
    check(all(altered) and blob.differs_at(0, whole + b"x") and blob.differs_at(blob.size, b"x"),
          "differs_at sees one altered byte at either end of either chunk, and a byte past the end")

    import reduce_trace

    reduced = reduce_trace.reduce(os.path.join(HERE, "fixtures", "one_upload.xplane.pb"))
    check("error" not in reduced and 0 < reduced["busy_s"] <= reduced["window_s"],
          f"fixture trace: 0 < busy_s {reduced.get('busy_s')} <= window_s "
          f"{reduced.get('window_s')}")
    check(any("sha256_tiles" in name for name, _ in reduced["device_ops"]),
          "fixture trace: the breakdown names sha256_tiles")
    check(abs(reduced["busy_s"] - 0.267932712) < 1e-9,
          "fixture trace: busy_s is the union of the XLA Ops line, 267.93 ms")
    check(reduce_trace.union([(0, 2), (1, 3), (5, 6), (5, 5.5)]) == [(0, 3), (5, 6)],
          "union of overlapping intervals")

    print("selfcheck: " + (f"{len(FAILED)} FAILED" if FAILED else "all passed"))
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
