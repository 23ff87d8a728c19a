"""Share of the clients' wall inside the window spent on the harness's own
side (making bytes, waiting for the producer) and not waiting on the
system. ``{"key": "gen_busy_s"}``"""


def read(ctx, params):
    wall = ctx["harness"]["clients"] * ctx["window"]["seconds"]
    return 100.0 * ctx["harness"][params["key"]] / wall
