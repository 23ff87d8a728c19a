"""Share of a memory roofline, in percent: the least time the chip could
take for the payload it had to hash while it was traced (each byte over HBM
once, at the published rate) over the time an operation ran on the device.
The payload is the window's own rate of completed operations' bytes, open
to close, over the traced seconds: the work the cell's semantics require,
never what an implementation chose to dispatch, and never more than was
done. ``{"peak": "hbm_bytes_per_s"}``"""


def read(ctx, params):
    trace = ctx["trace"]
    if not trace or not trace.get("payload_bytes"):
        return None
    least_s = trace["payload_bytes"] / ctx["peaks"][params["peak"]]
    return 100.0 * least_s / trace["busy_s"]
