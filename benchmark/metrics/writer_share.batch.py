"""IPC writer (``jobs/writer.py``, ``jobs/manifest.py``): the share of
the window's job time spent committing shards: table concat, IPC write
and manifest commit (the ``job_commit`` span over the ``job`` span)."""
from readers import stage_seconds


def read(ctx):
    job = stage_seconds(ctx, "job")
    if not job:
        return None
    return stage_seconds(ctx, "job_commit") / job
