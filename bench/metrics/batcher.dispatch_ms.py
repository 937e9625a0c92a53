"""Mean host time of one ``FilterOpBatcher`` wave dispatch: the program's
``wave_dispatch`` span (host prep, dedupe, padding, upload, the device
calls enqueued), in the traced window."""
import numpy as np


def read(ctx):
    d = ctx["reduced"].span_durations_s("wave_dispatch")
    return 1e3 * float(np.mean(d)) if d else None
