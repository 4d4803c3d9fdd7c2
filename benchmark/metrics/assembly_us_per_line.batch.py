"""Arrow assembly (``tpu/arrow_bridge.py``, ``tpu/hostpool.py``): host
microseconds per line in the ``columns`` and ``assembly`` stages over
the window.  The dashboard cell skips this layer."""
from readers import stage_seconds


def read(ctx):
    lines = ctx["facts"].get("lines")
    secs = stage_seconds(ctx, "columns") + stage_seconds(ctx, "assembly")
    if not lines or not secs:
        return None
    return 1e6 * secs / lines
