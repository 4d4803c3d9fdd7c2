"""Feed layer (``feeder/``): the share of the window's fed batches that
crossed in a frame-sized shared-memory segment, not a ring slot or the
pickled pipe (``feeder_segment_batches_total`` over
``feeder_batches_total``).  A program without the segment counter reads
nothing."""
from readers import counter_delta

COUNTER = "feeder_segment_batches_total"


def read(ctx):
    batches = counter_delta(ctx, "feeder_batches_total")
    if not batches or COUNTER not in ctx["after"]["counters"]:
        return None
    return counter_delta(ctx, COUNTER) / batches
