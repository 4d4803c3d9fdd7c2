"""Feed layer (``feeder/``, ``jobs/``): the share of the jobs' wall time
in which the parser waited on the feeder for a framed batch
(``feeder_starvation_seconds_total`` over the window)."""
from readers import counter_delta


def read(ctx):
    wall = ctx["facts"].get("wall_s")
    if not wall:
        return None
    return counter_delta(ctx, "feeder_starvation_seconds_total") / wall
