#!/usr/bin/env python3
"""``serve.py`` with the served path broken underneath, for test_correct.py:
an answer is altered where it is produced. ``BENCH_FAULT`` names which:

- ``piece_hash``: the device hasher's last digest of every ``hash_pieces``
  call has one bit flipped (the origin then serves a wrong metainfo);
- ``delivered_byte``: the agent flips one byte of a pulled blob's cache
  file before it answers the client.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import serve  # noqa: E402  (puts the repo on sys.path)

fault = os.environ["BENCH_FAULT"]
if fault == "piece_hash":
    from kraken_tpu.ops import sha256

    sound = sha256.JaxPieceHasher.hash_pieces

    def hash_pieces(self, data, piece_length):
        out = sound(self, data, piece_length).copy()
        out[-1, 0] ^= 1
        return out

    sha256.JaxPieceHasher.hash_pieces = hash_pieces
elif fault == "delivered_byte":
    from kraken_tpu.store import serve as store_serve

    sound = store_serve.blob_response

    async def blob_response(req, store, d):
        with open(store.cache_path(d), "r+b") as f:
            f.seek(os.path.getsize(store.cache_path(d)) // 2)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 1]))
        return await sound(req, store, d)

    store_serve.blob_response = blob_response
else:
    raise SystemExit(f"unknown BENCH_FAULT {fault!r}")

serve.main()
