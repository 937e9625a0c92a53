"""What every cell shares: finding its files by name, the device, the
compile counter, tracing, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
configuration's file (``configs[].file``) names its driver,
``bench/drivers/<driver>.py``; the mix is ``bench/traffic/<traffic>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``.  A later
change adds a cell, a configuration, a mix or a metric by adding such files
and entries, and edits none.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """The accelerator this cell needs is not there."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def spec(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def _load_module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    mod_name = "bench_plugin_" + "".join(c if c.isalnum() else "_"
                                         for c in name)
    sp = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    metrics_e2e: list            # end_to_end entries reported by this cell
    metrics_layer: list          # per_layer entries reported by this cell
    driver: object               # module with run(ctx) -> Outcome


def resolve(workload: str, root: str = ROOT) -> Cell:
    """Everything a cell is made of, found by the names in BENCHMARK.json."""
    b = spec(root)
    here = os.path.join(root, "bench")
    wl = _by_name(b["workloads"], workload, "workload")
    cf = _by_name(b["configs"], wl["config"], "configuration")
    config = load_json(os.path.join(root, cf["file"]))
    mix = load_json(os.path.join(here, "traffic", f"{wl['traffic']}.json"))
    driver = _load_module(os.path.join(here, "drivers",
                                       f"{config['driver']}.py"),
                          "driver_" + config["driver"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Cell(name=workload, chips=wl["chips"], config=config, mix=mix,
                metrics_e2e=[m for m in b["end_to_end"] if applies(m)],
                metrics_layer=[m for m in b["per_layer"] if applies(m)],
                driver=driver)


def metric_reader(name: str, root: str = ROOT):
    """``bench/metrics/<name>.py``'s ``read(ctx) -> float | None``."""
    return _load_module(os.path.join(root, "bench", "metrics", f"{name}.py"),
                        "metric_" + name).read


# ------------------------------------------------------------- device --


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks)) if peaks else 0


class GcPauses:
    """Collector pauses while on: count and longest, per generation."""

    def __init__(self):
        self.on = False
        self.pauses = {0: [], 1: [], 2: []}
        self._t = 0.0
        gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.pauses[info["generation"]].append(time.perf_counter()
                                                   - self._t)

    def summary(self) -> dict:
        return {f"gen{g}": [len(p), 1e3 * max(p, default=0.0)]
                for g, p in self.pauses.items()}


class CompileCounter:
    """Counts programs lowered (a new executable was needed) while on."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.on = False
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    @property
    def count(self) -> int:
        return len(self.names)

    def _hear(self, event, _secs, fun_name=None, **_kw):
        if self.on and event == self.EVENT:
            self.names.append(fun_name)


@dataclasses.dataclass
class Ctx:
    """What a driver gets."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                 # process start, perf_counter seconds
    trace_dir: str
    log: Callable = print
    require_tpu: bool = True
    compiles: Optional[CompileCounter] = None
    gc_pauses: Optional[GcPauses] = None

    @contextlib.contextmanager
    def window(self):
        """Bracket the measured window: compile and collector counting,
        and with ``trace`` the profiler and the ``bench_window`` span."""
        import jax
        for counter in (self.compiles, self.gc_pauses):
            if counter is not None:
                counter.on = True
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no event per Python call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench_window"):
                    yield
            finally:
                jax.profiler.stop_trace()
        else:
            yield
        for counter in (self.compiles, self.gc_pauses):
            if counter is not None:
                counter.on = False

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    metrics: dict                  # end-to-end name -> value
    checks: dict                   # compared name -> (value, limit)
    attempted: int
    failed: int
    devices: list
    memory_peak_bytes: int
    info: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)


def is_correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())


def stderr(*a):
    print(*a, file=sys.stderr, flush=True)
