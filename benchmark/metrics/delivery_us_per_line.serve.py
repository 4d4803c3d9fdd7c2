"""Arrow IPC and socket (``tpu/arrow_bridge.py``, ``service.py``): host
microseconds per answered line spent serializing each session's answer
to Arrow IPC (span ``ipc``) and writing it to the socket (span
``serve_send``), over the lines the service answered in the window
(``service_lines_total``).  None from a program without ``serve_send``."""
from readers import counter_delta, hist_delta, stage_seconds


def read(ctx):
    if not hist_delta(ctx, 'stage_seconds{stage="serve_send"}', "count"):
        return None
    lines = counter_delta(ctx, "service_lines_total")
    if not lines:
        return None
    return 1e6 * (stage_seconds(ctx, "ipc")
                  + stage_seconds(ctx, "serve_send")) / lines
