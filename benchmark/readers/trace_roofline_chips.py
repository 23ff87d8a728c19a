"""Share of a mesh's memory roofline, in percent: ``trace_roofline``'s form
for a cell whose device is several chips. The least time the chips could
take together for the payload they had to hash while they were traced (each
byte over HBM once, at the published rate of one chip times ``chips``) over
the time an operation ran on a chip, the mean of the device planes
(``busy_s``, as ``reduce_trace.py`` takes it). The payload is the window's
own rate of completed operations' bytes, open to close, over the traced
seconds: the work the cell's semantics require, never what an
implementation chose to dispatch or pad, and never more than was done.
``{"peak": "hbm_bytes_per_s", "chips": 4}``"""

from readers import trace_roofline


def read(ctx, params):
    of_one_chip = trace_roofline.read(ctx, params)
    return None if of_one_chip is None else of_one_chip / params["chips"]
