"""Median time the harvest of a wave waits for its device results
(``jax.block_until_ready``): the ``harvest_wait`` span, inside
``wave_harvest``, in the traced window."""
from bench import wave_spans


def read(ctx):
    return wave_spans.median_ms(ctx["reduced"], "harvest_wait")
