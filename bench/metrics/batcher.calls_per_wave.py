"""JAX program launches (``PjitFunction(...)``) and host->device transfers
(``DevicePut...`` on a TPU, ``BatchedCopyToDevice...`` on a CPU) made
inside the waves' ``wave_dispatch`` and ``wave_harvest`` spans, per wave,
in the traced window.  A count: no clock in it."""
from bench import wave_spans


def read(ctx):
    return wave_spans.calls_per_wave(ctx["reduced"])
