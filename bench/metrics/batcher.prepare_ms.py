"""Median host time of a wave's prep in ``FilterOpBatcher``: the
``wave_prepare`` span (lookup dedupe, padding to ``wave_slots``, the hash
split), inside ``wave_dispatch``, in the traced window."""
from bench import wave_spans


def read(ctx):
    return wave_spans.median_ms(ctx["reduced"], "wave_prepare")
