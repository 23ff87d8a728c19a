"""Share, in percent, of one counter's growth over the window in another's,
from the component's own /metrics: ``{"component", "numerator": {"metric",
"labels"}, "denominator": {...}}``. Both sides are the program's counters
over the same two scrapes, so what lies between them besides the window (the
drain, the tracer's writing) dilutes neither. Nothing counted, nothing
returned."""

from readers import prom_delta_ratio


def read(ctx, params):
    ratio = prom_delta_ratio.read(ctx, params)
    return None if ratio is None else 100.0 * ratio
