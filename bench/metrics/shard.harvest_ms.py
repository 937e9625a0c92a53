"""Median host time of a routed call's harvest: the ``shard_harvest`` span
(the wait for its device results and their fetch to the host, deferred
lanes parked), in the traced window."""
import numpy as np


def read(ctx):
    d = ctx["reduced"].span_durations_s("shard_harvest")
    return 1e3 * float(np.median(d)) if d else None
