"""Framing and H2D (``tpu/batch.py``): the share of the window's job
time spent waiting for staged batches to reach the device (the
``h2d_wait`` span over the ``job`` span)."""
from readers import stage_seconds


def read(ctx):
    job = stage_seconds(ctx, "job")
    if not job:
        return None
    return stage_seconds(ctx, "h2d_wait") / job
