"""Jobs runner (``jobs/runner.py``): the share of the window's job time
that lies under no phase span: the ``job`` root's self time
(``stage_self_seconds_total{stage="job"}``) over its inclusive time
(``stage_seconds{stage="job"}``)."""
from readers import counter_delta, stage_seconds


def read(ctx):
    job = stage_seconds(ctx, "job")
    if not job:
        return None
    return counter_delta(ctx, 'stage_self_seconds_total{stage="job"}') / job
