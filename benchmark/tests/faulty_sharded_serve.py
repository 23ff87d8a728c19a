#!/usr/bin/env python3
"""``serve.py`` with the sharded hasher's answers altered where they are
produced, for the cell ``origin-tpu-sharded.push-layers`` (``faulty_serve.py``
patches ``JaxPieceHasher`` only, which a ``tpu-sharded`` origin keeps for its
tails and the dedup pass): the last digest of every
``ShardedPieceHasher.hash_staged_window`` (a window of whole pieces) and of
every ``hash_pieces`` (a last window with its tail) has one bit flipped, so
every push is served a wrong metainfo and ``wrong_answers`` counts them all.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import serve  # noqa: E402  (puts the repo on sys.path)

from kraken_tpu.parallel.hashplane import ShardedPieceHasher  # noqa: E402


def _altered(sound):
    def hashed(self, *args):
        out = sound(self, *args).copy()
        out[-1, 0] ^= 1
        return out

    return hashed


ShardedPieceHasher.hash_staged_window = _altered(ShardedPieceHasher.hash_staged_window)
ShardedPieceHasher.hash_pieces = _altered(ShardedPieceHasher.hash_pieces)

serve.main()
