"""Oracle rescue (``tpu/batch.py`` ``_run_oracle_many``): the share of
the window's lines rerouted to the host oracle (the
``oracle_fallback`` stage's items)."""
from readers import stage_items


def read(ctx):
    lines = ctx["facts"].get("lines")
    if not lines:
        return None
    return stage_items(ctx, "oracle_fallback") / lines
