"""A quantile of one client-side span over every operation the window
started, those drained after its close too (leaving them out would leave out
the longest): ``{"from": "t_start", "to": "t_end", "q": 0.9}``."""

import numpy as np

from readers import answered


def read(ctx, params):
    spans = [r[params["to"]] - r[params["from"]] for r in answered(ctx)
             if params["from"] in r and params["to"] in r]
    if not spans:
        return None
    return float(np.percentile(spans, 100 * params["q"]))
