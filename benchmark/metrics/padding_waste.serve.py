"""Framing and H2D of the served path (``service_batching.py``, ``tpu/batch.py``): the share of the
[B, L] cells shipped to the device that are padding, over the window:
1 - encoded_line_bytes_total / buffer_cells_total."""
from readers import counter_delta


def read(ctx):
    cells = counter_delta(ctx, "buffer_cells_total")
    if not cells:
        return None
    return 1.0 - counter_delta(ctx, "encoded_line_bytes_total") / cells
