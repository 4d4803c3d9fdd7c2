"""Service and coalescer (``service_batching.py``): the share of the
window the coalescer's dispatcher thread spent parsing formed batches
(span ``coalesce_parse``, which holds the shared ``encode``,
``h2d_stage``, ``device``, ``fetch`` and ``columns`` spans) over the
window's ``wall_s``.  Near 1, the dispatcher sets the rate; well below,
it waits on the sessions.  None from a program without the span."""
from readers import stage_seconds


def read(ctx):
    busy = stage_seconds(ctx, "coalesce_parse")
    wall = ctx["facts"].get("wall_s")
    if not busy or not wall:
        return None
    return busy / wall
