"""Median host time of a wave's three host->device copies (keys' high and
low halves, the valid mask): the ``wave_upload`` span, inside
``wave_dispatch``, in the traced window."""
from bench import wave_spans


def read(ctx):
    return wave_spans.median_ms(ctx["reduced"], "wave_upload")
