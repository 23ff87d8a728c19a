"""The device's idle share of the traced stretch, in percent: 1 - busy_s /
window_s, both from the device trace. ``{}``"""


def read(ctx, params):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
