"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of operation intervals / window)."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["window_s"]:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
