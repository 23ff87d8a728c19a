"""Workload bytes from --seed, and the plain reference for them.

The reference is hashlib over the generator's own bytes: the blob digest
and every piece hash at the piece length the configuration's table gives
the blob's size. Nothing here imports the program.
"""

from __future__ import annotations

import copy
import hashlib

import numpy as np

MIB = 1 << 20
CHUNK = 16 * MIB  # a multiple of every piece length in use


def piece_length_for(size: int, table: list[list[int]]) -> int:
    """``table`` rows are (smallest blob size, piece length); last match wins."""
    chosen = table[0][1]
    for min_size, piece_length in table:
        if size >= min_size:
            chosen = piece_length
    return chosen


class SeededBlob:
    """``size`` bytes fixed by (seed, index) and the mix's ``bytes``. The
    blob's first ``salt_bytes`` are drawn from (seed, index); the body after
    them from (the mix's ``draw``, index), the same for every seed. So every
    digest and every piece's hash differs from seed to seed, while whatever
    the served path derives from a blob's content away from its head (where
    a content-defined chunker cuts, and so the shapes and the amount of the
    dedup pass's device work) is one amount of work for every seed (PERF.md
    section 4). Chunk k is the body's random block XORed with a per-chunk
    64-bit key, so a blob never sits in memory whole, no two blobs of a run
    share bytes, and nothing in it compresses."""

    def __init__(self, seed: int, index: int, size: int, piece_length: int,
                 body: dict):
        self.index = index
        self.size = size
        self.piece_length = piece_length
        words = -(-min(size, CHUNK) // 8)
        rng = np.random.default_rng([body["draw"], index])
        self._base = rng.integers(0, 1 << 64, size=max(words, 1), dtype=np.uint64)
        salt = np.random.default_rng([seed, index])
        self._salt = salt.integers(0, 1 << 64, size=-(-body["salt_bytes"] // 8),
                                   dtype=np.uint64)[:len(self._base)]
        self.hex = ""            # sha256 of the whole blob
        self.piece_hashes = b""  # 32 bytes a piece
        self._kept: tuple[int, bytes] | None = None  # differs_at's last chunk

    def chunk(self, k: int) -> bytes:
        key = np.uint64(((k + 1) * 0x9E3779B97F4A7C15) % (1 << 64))
        n = min(CHUNK, self.size - k * CHUNK)
        words = self._base ^ key
        if k == 0:
            words[:len(self._salt)] = self._salt
        return words.view(np.uint8)[:n].tobytes()

    @property
    def n_chunks(self) -> int:
        return -(-self.size // CHUNK)

    @property
    def n_pieces(self) -> int:
        return -(-self.size // self.piece_length)

    def compute_reference(self) -> None:
        whole = hashlib.sha256()
        pieces = []
        plen = self.piece_length
        for k in range(self.n_chunks):
            data = self.chunk(k)
            whole.update(data)
            view = memoryview(data)
            for off in range(0, len(data), plen):
                pieces.append(hashlib.sha256(view[off:off + plen]).digest())
        self.hex = whole.hexdigest()
        self.piece_hashes = b"".join(pieces)

    def stream(self) -> "SeededBlob":
        """The same blob with a compare cache of its own, for one reader. A
        rollout pulls one blob through every agent at once, and the readers
        compare from threads of their own: sharing one cache, each would
        evict the others' chunk, and one that reached the blob's end would
        drop it while another was reading it."""
        twin = copy.copy(self)
        twin._kept = None
        return twin

    def differs_at(self, offset: int, data: bytes) -> bool:
        """Whether ``data``, received at ``offset``, differs from the blob.
        A response arrives in pieces far smaller than a chunk, in order, so
        the chunk under comparison is kept until the next one is asked for:
        made anew for every piece, it cost more than the pull it checked
        (PERF.md section 6, PR 35: half of every pull's span on the chip's host)."""
        pos = 0
        while pos < len(data):
            k, within = divmod(offset + pos, CHUNK)
            if k >= self.n_chunks:
                return True
            if self._kept is None or self._kept[0] != k:
                self._kept = (k, self.chunk(k))
            want = memoryview(self._kept[1])[within:within + len(data) - pos]
            if not want or data[pos:pos + len(want)] != want:
                return True
            pos += len(want)
        if offset + len(data) >= self.size:
            self._kept = None  # the blob's last bytes: nothing left to keep it for
        return False
