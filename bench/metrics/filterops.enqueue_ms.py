"""Median host time of a wave's one table-op call into ``FilterOps``, until
its programs are enqueued: the ``filterops.<entry>`` spans (for example
``filterops.lookup_with_stash``), inside ``wave_dispatch``, in the traced
window."""
from bench import wave_spans


def read(ctx):
    return wave_spans.median_ms(ctx["reduced"], "filterops.", prefix=True)
