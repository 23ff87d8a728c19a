#!/usr/bin/env python3
"""``serve.py`` for the CPU rehearsal of ``origin-tpu-sharded.push-layers``:
the component's ``--config`` is replaced by a file that extends it and sets
``ingest.window_bytes`` to one 4 MiB piece. The traffic file's ``tiny`` deck
is four blobs of 4.5-14 MB, which the shipped 64 MiB window takes as one
window with a ragged tail each, so the ``transfer`` stage (whole-piece windows
only) would never run and ``ingest_transfer_s`` would find nothing to read.
With a window a piece, a blob is one to three whole windows (one row, padded
to the mesh's four) and a tail, as a layer is 1-11 whole windows and a last
one. Nothing of the program is patched: the override is the YAML an operator
would write.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import serve  # noqa: E402  (puts the repo on sys.path)

at = sys.argv.index("--config") + 1
shipped = sys.argv[at]
store = sys.argv[sys.argv.index("--store") + 1]
sys.argv[at] = os.path.join(os.path.dirname(store), "one_piece_window.yaml")
with open(sys.argv[at], "w") as f:
    f.write(f"extends: {shipped}\ningest:\n  window_bytes: 4194304\n")

serve.main()
