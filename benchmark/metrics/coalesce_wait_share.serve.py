"""Service and coalescer (``service.py``, ``service_batching.py``): the
share of the requests' time spent queued in the coalescer before a
formed batch claimed them, over the window: span ``coalesce_wait`` over
span ``serve_request`` (inclusive seconds).  None from a program
without the spans."""
from readers import stage_seconds


def read(ctx):
    total = stage_seconds(ctx, "serve_request")
    if not total:
        return None
    return stage_seconds(ctx, "coalesce_wait") / total
