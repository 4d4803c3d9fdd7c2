"""Feed layer (``feeder/``): the share of the window's job time spent
starting each job's feeder pool, waiting for its first batch and
closing it (the ``feeder_start``, ``feeder_fill`` and ``feeder_close``
spans over the ``job`` span)."""
from readers import stage_seconds


def read(ctx):
    job = stage_seconds(ctx, "job")
    if not job:
        return None
    return sum(stage_seconds(ctx, s) for s in
               ("feeder_start", "feeder_fill", "feeder_close")) / job
