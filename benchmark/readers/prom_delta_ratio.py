"""Ratio of two counters' growth over the window, from the component's own
/metrics: ``{"component", "numerator": {"metric", "labels"}, "denominator":
{...}}``. Nothing counted, nothing returned."""

from readers import prom_delta


def read(ctx, params):
    num = prom_delta(ctx, params["component"], **_of(params["numerator"]))
    den = prom_delta(ctx, params["component"], **_of(params["denominator"]))
    if num is None or not den:
        return None
    return num / den


def _of(side):
    return {"name": side["metric"], "labels": side.get("labels", {})}
