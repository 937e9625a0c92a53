"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer readers use.

Device planes are ``/device:TPU:<n>`` (a trace holds other planes named
``/device:...`` that are no chip); on each, the ``XLA Ops`` line holds one
event per operation run on the device, ``Async XLA Ops`` the asynchronous
ones (transfers, collectives), and the ``XLA Modules`` line one event per
program (jitted function) run.  The host plane ``/host:CPU``
holds one line per thread; the harness's thread carries the program's own
spans (``wave_dispatch``, ``wave_harvest``), the harness's, and JAX's
(``PjitFunction(...)``).  The harness marks its measured window with one
span on that thread, ``bench_window``; everything is clipped to it.

* busy: the union of the ``XLA Ops`` intervals of a device in the window.
* op time: the summed durations per op name, asynchronous ops included.
* program time: the summed ``XLA Modules`` durations per program name
  (the trailing ``(<id>)`` of a module name dropped).
* idle gaps: the stretches of the window in which device 0 ran no
  operation, each attributed to the innermost span of the harness's thread
  that holds its midpoint (``host idle`` where none does).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    n_devices: int
    busy_s: list                 # per device, seconds busy in the window
    programs: dict               # program name -> seconds, all devices
    ops: dict                    # op name -> seconds, all devices
    gaps: list                   # (seconds, host span) per idle gap, dev 0
    spans: list                  # (name, start_ns, end_ns), harness thread

    @property
    def mean_busy_s(self) -> float:
        return float(np.mean(self.busy_s)) if self.busy_s else 0.0

    def program_s(self, pattern: str) -> float:
        """Seconds of every program whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.programs.items() if rx.search(name))

    def span_durations_s(self, name: str) -> list:
        return [(e - s) / 1e9 for n, s, e in self.spans if n == name]

    def top_programs(self, k: int = 10) -> list:
        per_dev = max(1, self.n_devices)
        items = sorted(self.programs.items(), key=lambda kv: -kv[1])[:k]
        return [[name, s / per_dev] for name, s in items]

    def top_gaps(self, k: int = 10) -> list:
        return [[name, s] for s, name in sorted(self.gaps, reverse=True)[:k]]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _clip(evs, w0, w1):
    return [(n, max(s, w0), min(e, w1)) for n, s, e in evs if s < w1 and e > w0]


def _merge(iv) -> list:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans, points):
    """Name of the innermost (nested) span holding each sorted point."""
    evs = sorted(spans, key=lambda t: (t[1], -t[2]))
    out, stack, i = [], [], 0
    for p in points:
        while i < len(evs) and evs[i][1] <= p:
            while stack and stack[-1][2] <= evs[i][1]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][2] <= p:
            stack.pop()
        out.append(stack[-1][0] if stack else "host idle")
    return out


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce_planes(planes) -> Reduced:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``) — what
    ``jax.profiler.ProfileData`` gives."""
    harness, devices = None, []
    for plane in planes:
        if plane.name.startswith("/host:CPU") and harness is None:
            for line in plane.lines:
                if any(e.name == WINDOW_SPAN for e in line.events):
                    harness = _events(line)
                    break
        elif _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops, async_ops, mods = (_events(lines[k]) if k in lines else []
                                    for k in ("XLA Ops", "Async XLA Ops",
                                              "XLA Modules"))
            devices.append((plane.name, ops, async_ops, mods))
    if harness is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = next((s, e) for n, s, e in harness if n == WINDOW_SPAN)
    spans = [t for t in _clip(harness, w0, w1) if t[0] != WINDOW_SPAN]
    devices.sort(key=lambda d: int(re.sub(r"\D", "", d[0]) or 0))
    busy, programs, op_s, gaps = [], {}, {}, []
    for k, (_name, ops, async_ops, mods) in enumerate(devices):
        ops = _clip(ops, w0, w1)
        merged = _merge([s, e] for _n, s, e in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for n, s, e in ops + _clip(async_ops, w0, w1):
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9
        for n, s, e in _clip(mods, w0, w1):
            n = _MODULE_ID.sub("", n)
            programs[n] = programs.get(n, 0.0) + (e - s) / 1e9
        if k == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            holes = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
            names = _innermost(spans, [(s + e) / 2 for s, e in holes])
            gaps = [((e - s) / 1e9, n) for (s, e), n in zip(holes, names)]
    return Reduced(window_s=(w1 - w0) / 1e9, n_devices=len(devices),
                   busy_s=busy, programs=programs, ops=op_s, gaps=gaps,
                   spans=spans)


def reduce_file(path: str) -> Reduced:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes)
