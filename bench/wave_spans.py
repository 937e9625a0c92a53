"""The steps of the batcher's waves in a reduced trace.

``FilterOpBatcher`` records each wave as a ``wave_dispatch`` and a
``wave_harvest`` span on the harness's thread, with the steps of the wave
as spans nested inside them (``wave_prepare``, ``wave_upload``,
``filterops.<entry>``, ``wave_occupancy``; ``harvest_wait``,
``harvest_fetch``).  The readers here work on ``Reduced.spans``, the
(name, start_ns, end_ns) events of that thread, and find what lies inside
a wave by interval containment.  A trace with no waves reads ``None``.
"""
from __future__ import annotations

import bisect
import re

import numpy as np

WAVES = ("wave_dispatch", "wave_harvest")
# JAX's own events for a program launch and a host->device transfer (the
# transfer's name differs by platform).  A nested event of the same family
# (a PjitFunction's inner PjitFunction, a transfer's phases) is not counted.
CALL_FAMILIES = (re.compile(r"^PjitFunction\("),
                 re.compile(r"^(DevicePut|BatchedCopyToDevice"
                            r"|batched_copy_array_to_devices)"))


def in_waves(spans) -> list:
    """The spans that lie inside a wave's dispatch or harvest span (the
    wave spans themselves left out)."""
    outer = sorted((s, e) for n, s, e in spans if n in WAVES)
    starts = [s for s, _e in outer]
    out = []
    for t in spans:
        i = bisect.bisect_right(starts, t[1]) - 1
        if i >= 0 and t[2] <= outer[i][1] and t[0] not in WAVES:
            out.append(t)
    return out


def median_ms(reduced, name: str, *, prefix: bool = False):
    """Median milliseconds of the step spans called ``name`` (or starting
    with it, with ``prefix``) inside the waves, or None where there are
    none."""
    d = [e - s for n, s, e in in_waves(reduced.spans)
         if (n.startswith(name) if prefix else n == name)]
    return float(np.median(d)) / 1e6 if d else None


def _outermost(events) -> int:
    """How many of ``events`` lie inside no other of them."""
    count, end = 0, None
    for _n, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        if end is None or s >= end:
            count, end = count + 1, e
        else:
            end = max(end, e)
    return count


def calls_per_wave(reduced):
    """JAX program launches and host->device transfers made inside the
    waves, per wave (a wave is one ``wave_dispatch``), or None."""
    n_waves = sum(1 for n, _s, _e in reduced.spans if n == WAVES[0])
    if not n_waves:
        return None
    inner = in_waves(reduced.spans)
    calls = sum(_outermost([t for t in inner if rx.match(t[0])])
                for rx in CALL_FAMILIES)
    return calls / n_waves
