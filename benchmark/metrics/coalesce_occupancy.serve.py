"""Service and coalescer (``service.py``, ``service_batching.py``): the
mean fill of the batches the coalescer formed in the window, as a share
of ``coalesce_max_lines`` (``service_coalesce_batch_occupancy``)."""
from readers import hist_delta

SERIES = "service_coalesce_batch_occupancy"


def read(ctx):
    n = hist_delta(ctx, SERIES, "count")
    if not n:
        return None
    return hist_delta(ctx, SERIES, "sum") / n
