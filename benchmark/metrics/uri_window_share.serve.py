"""Kernel (``tpu/postproc.py`` ``split_uri_fast``, ``tpu/batch.py``): the
share of the lines parsed in the window that rode in a batch whose line
bucket passes the URI split's fixed 1,536-byte scan window, so the
split cut that window out of each of them: the window delta of
``uri_window_lines_total`` over that of ``parse_lines_total``.  None
from a program without the counter."""
from readers import counter_delta

COUNTER = "uri_window_lines_total"


def read(ctx):
    if COUNTER not in ctx["after"]["counters"]:
        return None
    lines = counter_delta(ctx, "parse_lines_total")
    if not lines:
        return None
    return counter_delta(ctx, COUNTER) / lines
