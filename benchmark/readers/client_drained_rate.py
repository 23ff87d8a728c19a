"""Payload completed a second, over all the work the window started and all
the time it took: the bytes of every operation the window started and the
system answered, those drained after its close too, over the seconds from
the window's open until the last of them ended. For operations that are long
against the window and whose bytes arrive at their end (a pulled blob is
delivered when its download is complete), what lands between the open and the
close is a step function of which blobs end just before the close: the same
runs read 48.9-79.2 MB/s that way and 71.3-80.2 this way (PERF.md section 2,
PR 35). ``scale`` multiplies (1e-6 for MB)."""

from readers import answered


def read(ctx, params):
    ops = answered(ctx)
    if not ops:
        return None
    seconds = max(r["t_end"] for r in ops) - ctx["window"]["t_open"]
    return sum(r["bytes"] for r in ops) * params.get("scale", 1.0) / seconds
