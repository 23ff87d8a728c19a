"""Work inside the window over the whole window, counted exactly:
``{"count": "bytes"}`` is the payload that crossed the wire between the
window's open and its close (the load's own byte count read at both, so an
operation in flight at either edge counts for what it moved inside);
``{"count": "ops"}`` is the operations that ended between the two.
``scale`` multiplies (1e-6 for MB)."""


from readers import answered


def read(ctx, params):
    window = ctx["window"]
    if params["count"] == "bytes":
        total = window["bytes_moved"]
    else:
        total = sum(1 for r in answered(ctx) if r["t_end"] <= window["t_close"])
    return total * params.get("scale", 1.0) / window["seconds"]
