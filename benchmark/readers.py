"""What per-layer readers share: window deltas of the program's
counters and histograms, from the registry snapshots the harness takes
at the window's start (``before``) and end (``after``)."""


def _family(snap, name):
    return sum(v for k, v in snap.items()
               if k == name or k.startswith(name + "{"))


def counter_delta(ctx, name):
    return (_family(ctx["after"]["counters"], name)
            - _family(ctx["before"]["counters"], name))


def counter_at_start(ctx, name):
    return _family(ctx["before"]["counters"], name)


def hist_delta(ctx, series, key):
    """Window delta of a histogram's ``count`` or ``sum``."""
    a = ctx["after"]["histograms"].get(series, {}).get(key, 0)
    b = ctx["before"]["histograms"].get(series, {}).get(key, 0)
    return a - b


def stage_seconds(ctx, stage):
    return hist_delta(ctx, 'stage_seconds{stage="%s"}' % stage, "sum")


def stage_items(ctx, stage):
    return counter_delta(ctx, 'stage_items_total{stage="%s"}' % stage)
