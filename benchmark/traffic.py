"""The one general traffic generator: a mix is a data file of parameters.

A mix names an operation (``push`` or ``pull``), a number of closed-loop
clients and a *deck*: the fixed sequence of blob sizes every seed is dealt,
and under ``bytes`` how a blob's content is drawn (``blobs.SeededBlob``): the
seed decides each blob's first ``salt_bytes``, so every digest and piece hash,
and nothing that decides how much work a blob is, so two seeds offer the same
work.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_traffic(name: str, scale: str = "real") -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if scale != "real":
        mix.update(mix.get(scale, {}))
    return mix


def deck_sizes(mix: dict) -> list[int]:
    """The deck as the file gives it: ``count`` blob sizes drawn once,
    uniformly in log(bytes) over the closed range, by the generator the
    file names: the same for every seed."""
    spec = mix["deck"]["log_uniform"]
    lo, hi = math.log(spec["min_bytes"]), math.log(spec["max_bytes"])
    rng = np.random.default_rng([spec["draw"], 11])
    return [
        min(spec["max_bytes"], max(spec["min_bytes"], round(math.exp(x))))
        for x in rng.uniform(lo, hi, spec["count"])
    ]


def deal(mix: dict) -> list[int]:
    """One pass over the deck, in the order every seed is dealt: blob sizes
    in bytes. The order is a shuffle fixed in the mix's file: where one
    device serves a closed loop, the order of the sizes decides what queues
    behind what, so an order drawn from the seed changes the work (PERF.md
    section 4: p90 moved 10% from seed to seed and 0.1% within one)."""
    sizes = deck_sizes(mix)
    rng = np.random.default_rng([mix["deck"]["order"], 7])
    return [sizes[i] for i in rng.permutation(len(sizes))]
