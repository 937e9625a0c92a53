"""Median host time of a wave's results, count and stash occupancy brought
to the host and its answers mapped back to the request's keys: the
``harvest_fetch`` span, inside ``wave_harvest``, in the traced window."""
from bench import wave_spans


def read(ctx):
    return wave_spans.median_ms(ctx["reduced"], "harvest_fetch")
