"""Kernel (``tpu/pipeline.py``): the parse and aggregate executables'
share of the HBM roofline.  The work is the raw log bytes the window's
jobs handed to the device, counted from the corpus (not the padded
buffer), so it reads the same whatever implements the kernel; the least
time for it is one read of those bytes at the chip's peak HBM
bandwidth (``peaks.json``).  The time is the device time of every XLA
executable in the traced window: in this cell the device runs only the
parse step and the aggregate reduction.  Bound by bytes: the parse does
no arithmetic to speak of.  In percent."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if trace is None or peaks is None:
        return None
    device_s = sum(trace["module_s"].values())
    raw = ctx["facts"].get("raw_bytes")
    if not device_s or not raw:
        return None
    return 100.0 * raw / (device_s * peaks["hbm_bytes_per_s"])
