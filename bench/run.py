"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``).  The run builds the
table on the device from ``--seed``, warms up every shape the window uses,
measures for ``--seconds``, then checks every answer of the window against
the plain reference (``bench/reference.py``).  With ``--trace 1`` the window
runs under the profiler and the per-layer metrics are reported instead of
the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``, each number compared beside its limit.
The same numbers end standard error.  Without a TPU, or with fewer chips
than the cell needs, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def trace_dir(workload: str, seed: int, root: str = ROOT) -> str:
    return os.path.join(root, ".chip_scratch", "trace", f"{workload}-{seed}")


def result_line(cell, ctx, out, *, layer_values=None, reduced=None) -> dict:
    dev = out.devices[0]
    res = {"correct": harness.is_correct(out.checks),
           "attempted": int(out.attempted), "failed": int(out.failed)}
    if ctx.trace:
        res["metrics"] = {m["name"]: {"value": layer_values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.metrics_layer
                          if layer_values.get(m["name"]) is not None}
    else:
        res["metrics"] = {m["name"]: {"value": out.metrics[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.metrics_e2e}
    res["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(out.devices),
                     "memory_peak_bytes": int(out.memory_peak_bytes)}
    if reduced is not None:
        res["device"]["busy_s"] = reduced.mean_busy_s
        res["device"]["window_s"] = reduced.window_s
        res["breakdown"] = {"device_ops": reduced.top_programs(10),
                            "idle_gaps": reduced.top_gaps(10)}
    res["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in out.checks.items()}
    return res


def measure(workload: str, *, seed: int, seconds: float, trace: bool,
            root: str = ROOT, require_tpu: bool = True, t_start=None,
            config_override=None, mix_override=None, cache: bool = True):
    """One run of a cell -> (cell, ctx, outcome, per-layer values, reduced
    trace or None)."""
    cell = harness.resolve(workload, root)
    if config_override:
        cell.config = {**cell.config, **config_override}
    if mix_override:
        cell.mix = {**cell.mix, **mix_override}
    devs = harness.devices_for(cell.chips, require_tpu)
    d = devs[0]
    harness.stderr(f"device: {d.platform} {d.device_kind}, count "
                   f"{len(devs)} of {len(__import__('jax').devices())}, at "
                   f"{time.perf_counter() - T_START:.3f} s")
    if cache:
        from repro.launch.compile_cache import enable_compile_cache
        harness.stderr(f"compile cache: {enable_compile_cache()}")
    ctx = harness.Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=T_START if t_start is None else t_start,
                      trace_dir=trace_dir(workload, seed, root),
                      log=harness.stderr, require_tpu=require_tpu,
                      compiles=harness.CompileCounter(),
                      gc_pauses=harness.GcPauses())
    out = cell.driver.run(ctx)
    layer_values, reduced = {}, None
    if trace:
        from bench import peaks, trace_reduce
        reduced = trace_reduce.reduce_file(
            trace_reduce.find_xplane(ctx.trace_dir))
        rctx = {"reduced": reduced, "counters": out.counters,
                "config": cell.config,
                "peaks": peaks.peaks(d.device_kind) if require_tpu else None}
        for m in cell.metrics_layer:
            layer_values[m["name"]] = harness.metric_reader(m["name"],
                                                            root)(rctx)
        top = sorted(reduced.ops.items(), key=lambda kv: -kv[1])[:12]
        out.info["device_ops_top"] = [[n[:100], s] for n, s in top]
    out.info["gc_pauses_in_window"] = ctx.gc_pauses.summary()
    for k, v in out.info.items():
        harness.stderr(f"info {k}: {v}")
    for k, (v, lim) in out.checks.items():
        harness.stderr(f"check {k}: {v} limit {lim}")
    return cell, ctx, out, layer_values, reduced


def run_cell(workload: str, **kw) -> dict:
    """One run of a cell -> its result line (a dict); ``kw`` as
    ``measure``'s."""
    cell, ctx, out, layer_values, reduced = measure(workload, **kw)
    return result_line(cell, ctx, out, layer_values=layer_values,
                       reduced=reduced)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        res = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace))
    except harness.NoChip as e:
        harness.stderr(f"bench: {e}; this benchmark runs only on a TPU")
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
