"""A time the harness took itself: ``{"key": "ready_s"}``."""


def read(ctx, params):
    return ctx["harness"].get(params["key"])
