"""Serving-path benchmark: OCF prefix-index ops at request rates, plus the
latency-SLO scenario suite (ISSUE 8).

Two entry points:

  * ``run()`` — the legacy request-rate rows (prefix index + OCF lookup
    stream), consumed by ``benchmarks/run.py``.  The SLO scenario matrix
    itself is emitted into ``BENCH_filter.json`` by
    ``benchmarks/filter_bench.py`` (one canonical trajectory file, one
    gate).
  * the CLI — interactive scenario replay:

        PYTHONPATH=src python benchmarks/serving_bench.py \
            --scenario burst_train --seed 0 [--sync]

    prints the scenario's p50/p99/p99.9 (overall and per op kind),
    keys/s, and the admission/shed counters.  ``--scenario all`` runs the
    full matrix exactly as the bench writes it.

Determinism: every stream derives from ONE ``np.random.Generator`` seeded
by ``--seed`` (``repro.serving.workloads.scenario_stream``); two runs at
one seed replay byte-identical key streams (tier-1-tested in
``tests/test_slo.py``).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import OCF, OcfConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.kvcache import PrefixCacheIndex
from repro.serving.slo import (BENCH_SCENARIOS, bench_scenarios,
                               run_scenario, run_scenario_telemetry)
from repro.serving.workloads import SCENARIOS, scenario_stream


def make_streams(seed: int, *, wave_slots: int = 512,
                 scenarios=tuple(SCENARIOS)) -> dict:
    """scenario -> materialized OpBatch stream, all from one seed.

    The seed-reproducibility audit point: everything the SLO bench
    replays flows through here (or ``run_scenario``, which builds the
    identical stream), so asserting two calls of this are byte-equal
    pins the whole suite's determinism.
    """
    return {name: scenario_stream(name, seed, wave_slots=wave_slots)
            for name in scenarios}


def run(seed: int = 0):
    """Legacy request-rate rows (run.py section ``prefix_* / ocf_*``)."""
    rows = []
    rng = np.random.default_rng(seed)

    # prefix-index ops at serving rates
    idx = PrefixCacheIndex(block=64)
    prompts = [rng.integers(0, 32000, 2048).astype(np.int32)
               for _ in range(64)]
    t0 = time.perf_counter()
    for p in prompts:
        idx.admit(p)
    t_admit = (time.perf_counter() - t0) / len(prompts)
    t0 = time.perf_counter()
    for p in prompts:
        idx.match_prefix(p)
    t_match = (time.perf_counter() - t0) / len(prompts)
    rows.append(("prefix_admit_per_request", t_admit * 1e6, idx.ocf.capacity))
    rows.append(("prefix_match_per_request", t_match * 1e6,
                 round(idx.hit_rate, 3)))

    # bursty lookup stream against one OCF node (the paper's workload)
    ocf = OCF(OcfConfig(capacity=1 << 14, mode="EOF"))
    keys = rng.integers(0, 2 ** 63, size=1 << 15, dtype=np.uint64)
    ocf.insert(keys)
    q = rng.permutation(np.concatenate([keys, keys]))[: 1 << 15]
    t0 = time.perf_counter()
    hits = ocf.lookup(q)
    dt = time.perf_counter() - t0
    rows.append(("ocf_lookup_stream", dt / q.size * 1e6, int(hits.sum())))
    return rows


def _print_report(rep, *, arm: str) -> None:
    p = rep.percentiles_us
    print(f"{rep.scenario} [{arm}]: {rep.ops} ops in {rep.wall_s:.3f}s "
          f"({rep.keys_per_s:,.0f} keys/s)")
    print(f"  p50 {p['p50']:>10.1f} us   p99 {p['p99']:>10.1f} us   "
          f"p99.9 {p['p999']:>10.1f} us")
    for kind, kp in sorted(rep.per_kind.items()):
        print(f"  {kind:>7}: p50 {kp['p50']:>10.1f}  p99 {kp['p99']:>10.1f}"
              f"  p99.9 {kp['p999']:>10.1f}")
    if rep.deferred_waves or rep.shed_ops:
        print(f"  admission: deferred_waves={rep.deferred_waves} "
              f"held_ticks={rep.held_ticks} shed_ops={rep.shed_ops}")
    for k, v in rep.extras.items():
        print(f"  {k}: {v}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default=None,
                    choices=sorted(SCENARIOS) + ["all"],
                    help="replay one SLO scenario (or 'all' for the "
                         "BENCH_filter.json matrix)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the single np.random.Generator every "
                         "stream derives from (byte-reproducible replays)")
    ap.add_argument("--sync", action="store_true",
                    help="force the synchronous submit path")
    ap.add_argument("--double-buffer", action="store_true",
                    help="force the double-buffered submit path (default: "
                         "auto — async only where the host can overlap)")
    ap.add_argument("--telemetry", action="store_true",
                    help="replay with device counter planes + trace spans "
                         "on; writes slo_<scenario>_metrics.jsonl and a "
                         "perfetto-loadable slo_<scenario>_trace.json into "
                         "--telemetry-dir")
    ap.add_argument("--telemetry-dir", default=".",
                    help="directory for --telemetry artifacts")
    args = ap.parse_args()
    enable_compile_cache()

    if args.scenario == "all":
        if args.telemetry:
            for name in BENCH_SCENARIOS:
                rep, paths = run_scenario_telemetry(
                    name, args.telemetry_dir, seed=args.seed)
                _print_report(rep, arm="telemetry")
                print(f"  metrics: {paths['metrics']}")
                print(f"  trace:   {paths['trace']}")
            return
        for k, v in bench_scenarios(args.seed).items():
            print(f"{k},{v}")
        return
    if args.scenario:
        db = "auto"
        if args.sync:
            db = False
        elif args.double_buffer:
            db = True
        if args.telemetry:
            rep, paths = run_scenario_telemetry(
                args.scenario, args.telemetry_dir, seed=args.seed,
                double_buffer=db)
            _print_report(rep, arm="telemetry")
            print(f"  metrics: {paths['metrics']}")
            print(f"  trace:   {paths['trace']}")
            return
        rep = run_scenario(args.scenario, seed=args.seed, double_buffer=db)
        arm = {False: "sync", True: "double-buffered"}.get(db, "auto")
        _print_report(rep, arm=arm)
        return
    print("name,us_per_call,derived")
    for name, us, derived in run(seed=args.seed):
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
