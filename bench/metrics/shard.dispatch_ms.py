"""Median host time of a call's dispatch through the sharded filter's
entry point (``DeferredWritePump``): the ``shard_dispatch`` span (hash
split and padding, the upload into the mesh's sharding, the routed program
enqueued), in the traced window."""
import numpy as np


def read(ctx):
    d = ctx["reduced"].span_durations_s("shard_dispatch")
    return 1e3 * float(np.median(d)) if d else None
