"""Median host time of enqueueing a wave's stash-occupancy count after the
op (and a table delete's count arithmetic): the ``wave_occupancy`` span,
inside ``wave_dispatch``, in the traced window."""
from bench import wave_spans


def read(ctx):
    return wave_spans.median_ms(ctx["reduced"], "wave_occupancy")
