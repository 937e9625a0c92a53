#!/usr/bin/env python
"""Bench-regression gate: fail when a fresh ``BENCH_filter.json`` shows any
``*_keys_per_s`` row more than ``THRESHOLD`` below the committed one.

Run by ``scripts/verify.sh`` right after the filter_bench smoke (which
rewrites ``BENCH_filter.json`` at the repo root); compares against the
version committed at HEAD via ``git show``, so the gate always measures
against the trajectory the repo actually promises.  A PR that slows a hot
path >20% must either fix the regression or consciously commit the slower
numbers (changing the baseline in the same commit clears the gate).

Since ISSUE 8 the gate also covers **tail latency**: every committed
``slo_*_p99_us`` row may rise at most ``BENCH_GATE_SLO_THRESHOLD``
(fraction, default 0.25) over its baseline, the scenario matrix must be
present (p50/p99/p999 for the core scenarios), and the double-buffered
burst tail must not fall behind the synchronous arm measured in the same
run.  Latency percentiles are single-pass samples (no min-of-trials — a
percentile of minima isn't a percentile), so they are noisier than the
keys/s rows; CI sets the SLO threshold generously and the local default
stays tight.

Since ISSUE 9 the gate also holds the **telemetry contract**: the fresh
``telemetry_overhead_pct`` row (telemetry-on vs -off arms of the same
serving-wave stream, same run) must stay at or below
``BENCH_GATE_TELEMETRY_PCT`` percent (default 5).

Exit codes: 0 pass / 1 regression / 0 with a notice when there is no
committed baseline (first run) or no git.  ``BENCH_GATE_THRESHOLD``
overrides the drop threshold (fraction, default 0.20) — the CPU container
rows are minima over interleaved trials, but a loaded machine can still
dip; raise the threshold there rather than deleting the gate.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
THRESHOLD = float(os.environ.get("BENCH_GATE_THRESHOLD", "0.20"))
SLO_THRESHOLD = float(os.environ.get("BENCH_GATE_SLO_THRESHOLD", "0.25"))
TELEMETRY_PCT = float(os.environ.get("BENCH_GATE_TELEMETRY_PCT", "5.0"))

# Scenarios whose percentile rows must exist in every fresh bench run
# (ISSUE 8 acceptance: the matrix can't silently shrink).
SLO_REQUIRED = ("uniform", "zipfian", "burst_train", "delete_heavy")


def main() -> int:
    fresh_path = os.path.join(REPO, "BENCH_filter.json")
    with open(fresh_path) as f:
        fresh = json.load(f)
    try:
        committed = json.loads(subprocess.check_output(
            ["git", "-C", REPO, "show", "HEAD:BENCH_filter.json"],
            text=True, stderr=subprocess.DEVNULL))
    except (subprocess.CalledProcessError, FileNotFoundError):
        print("bench gate: no committed BENCH_filter.json baseline; skipping")
        return 0
    bad = []
    for key, base in sorted(committed.items()):
        if not key.endswith("_keys_per_s") or not isinstance(base, (int,
                                                                    float)):
            continue
        cur = fresh.get(key)
        if cur is None:
            bad.append(f"  {key}: row disappeared (baseline {base})")
            continue
        if base > 0 and cur < base * (1.0 - THRESHOLD):
            bad.append(f"  {key}: {cur} vs baseline {base} "
                       f"({cur / base - 1.0:+.0%}, limit -{THRESHOLD:.0%})")
    # PR-6 acceptance: the routed distributed insert must beat the host-loop
    # baseline measured IN THE SAME RUN (not vs the committed file — both
    # arms see identical machine weather, so this comparison is noise-free
    # in a way the cross-run threshold can't be).
    routed = fresh.get("distributed_insert_pallas_keys_per_s")
    hostloop = fresh.get("distributed_insert_hostloop_keys_per_s")
    if routed is not None and hostloop is not None and routed <= hostloop:
        bad.append(f"  distributed_insert: routed {routed} keys/s does not "
                   f"beat the host-loop baseline {hostloop} keys/s")
    # PR-7 acceptance: false-positive-rate gates, same-run like the routed/
    # hostloop pair (rates at a fixed seed are deterministic, so these are
    # exact, not thresholds-with-noise).
    #   * ceiling: every fp_rate_* row must stay below 4x the partial-key
    #     expectation 2b/2^f (b=4 slots, two buckets, fp_rate_fp_bits) —
    #     a hash-quality tripwire, generous enough for binomial wobble;
    #   * ratio: after feedback the adaptive filter's FPR on the replayed
    #     adversarial mix must be >= 10x below the static filter's.
    fpb = fresh.get("fp_rate_fp_bits")
    if fpb is not None:
        ceiling = 4.0 * (2 * 4) / (1 << int(fpb))
        for key in ("fp_rate_static_uniform", "fp_rate_adaptive_uniform",
                    "fp_rate_static_adversarial",
                    "fp_rate_adaptive_adversarial"):
            rate = fresh.get(key)
            if rate is None:
                bad.append(f"  {key}: row missing from fresh bench")
            elif rate > ceiling:
                bad.append(f"  {key}: {rate:.2e} above ceiling "
                           f"{ceiling:.2e} (fp_bits={fpb})")
        stat = fresh.get("fp_rate_static_adversarial")
        adap = fresh.get("fp_rate_adaptive_adversarial")
        if stat is not None and adap is not None and adap * 10.0 > stat:
            bad.append(f"  fp_rate adversarial: adaptive {adap:.2e} not "
                       f">=10x below static {stat:.2e} after feedback")
    # ISSUE-8 acceptance: tail-latency gates.
    #   * regression: committed slo_*_p99_us rows may rise at most
    #     SLO_THRESHOLD (latency regresses UP — the mirror of keys/s);
    #   * presence + sanity: the core scenarios' percentile rows must
    #     exist and be ordered p50 <= p99 <= p999;
    #   * double-buffer win: the async burst tail must not fall behind
    #     the sync arm measured in the SAME run (same machine weather).
    for key, base in sorted(committed.items()):
        if not key.endswith("_p99_us") or not isinstance(base, (int, float)):
            continue
        if "_async_" in key:
            # observability-only arm: on single-core hosts the pipelined
            # path pays pure queueing, so its absolute value is volatile;
            # the default-vs-sync same-run check below is the contract.
            continue
        cur = fresh.get(key)
        if cur is None:
            bad.append(f"  {key}: row disappeared (baseline {base})")
        elif base > 0 and cur > base * (1.0 + SLO_THRESHOLD):
            bad.append(f"  {key}: {cur} us vs baseline {base} us "
                       f"({cur / base - 1.0:+.0%}, limit "
                       f"+{SLO_THRESHOLD:.0%})")
    for scen in SLO_REQUIRED:
        p = {q: fresh.get(f"slo_{scen}_{q}_us") for q in ("p50", "p99",
                                                          "p999")}
        if any(v is None for v in p.values()):
            bad.append(f"  slo_{scen}: percentile rows missing from "
                       f"fresh bench ({p})")
        elif not (p["p50"] <= p["p99"] <= p["p999"]):
            bad.append(f"  slo_{scen}: percentiles not monotone ({p})")
    dflt_p99 = fresh.get("slo_burst_train_p99_us")
    sync_p99 = fresh.get("slo_burst_train_sync_p99_us")
    if (dflt_p99 is not None and sync_p99 is not None and sync_p99 > 0
            and dflt_p99 > sync_p99 * (1.0 + SLO_THRESHOLD)):
        bad.append(f"  burst_train: default submit path p99 {dflt_p99} us "
                   f"fell behind the sync arm {sync_p99} us (same-run, "
                   f"limit +{SLO_THRESHOLD:.0%})")
    # ISSUE-10 acceptance: elastic resharding recovery rows.  These are
    # structural/correctness gates, not noise-tolerant thresholds: a
    # migration that loses a key or strands a parked write is broken at any
    # speed.  The elastic_*_keys_per_s rows additionally ride the generic
    # regression threshold above.
    for key in ("elastic_split_keys_per_s", "elastic_merge_keys_per_s",
                "elastic_time_to_recover_s", "elastic_shard_restore_s",
                "elastic_deferred_backlog_after",
                "elastic_migration_failed"):
        if fresh.get(key) is None:
            bad.append(f"  {key}: recovery row missing from fresh bench")
    for key in ("elastic_split_false_negatives",
                "elastic_merge_false_negatives",
                "elastic_degraded_false_negatives",
                "elastic_recover_false_negatives",
                "elastic_migration_failed",
                "elastic_deferred_backlog_after"):
        v = fresh.get(key)
        if v is not None and v != 0:
            bad.append(f"  {key}: {v} != 0 — elastic migration/recovery "
                       f"must be lossless and fully drained")
    ttr = fresh.get("elastic_time_to_recover_s")
    if ttr is not None and not 0.0 < ttr < 600.0:
        bad.append(f"  elastic_time_to_recover_s: {ttr} not in (0, 600)s "
                   f"— must be reported and sane")
    # ISSUE-9 acceptance: telemetry must stay near-free on the wave path.
    # ``telemetry_overhead_pct`` compares the telemetry-on and -off arms of
    # the SAME mixed wave stream measured in the same run (fresh batcher per
    # arm, arms alternated per trial), so like the routed/hostloop pair the
    # comparison is weather-free; the raw per-op rows stay informational
    # because the CPU emulation re-materializes gather chains the fused TPU
    # probe would not (see benchmarks/filter_bench.py::telemetry_rows).
    tel = fresh.get("telemetry_overhead_pct")
    if tel is None:
        bad.append("  telemetry_overhead_pct: row missing from fresh bench")
    elif tel > TELEMETRY_PCT:
        bad.append(f"  telemetry_overhead_pct: {tel}% wave-path overhead "
                   f"above the {TELEMETRY_PCT}% ceiling "
                   f"(BENCH_GATE_TELEMETRY_PCT overrides)")
    if bad:
        print(f"bench gate FAILED ({len(bad)} row(s) regressed "
              f">{THRESHOLD:.0%}):")
        print("\n".join(bad))
        print("fix the regression, or commit the new BENCH_filter.json as "
              "the intended baseline; on a host slower than the one that "
              "produced the baseline, set BENCH_GATE_THRESHOLD higher.")
        return 1
    n = sum(1 for k in committed if k.endswith("_keys_per_s"))
    n_slo = sum(1 for k in committed if k.endswith("_p99_us"))
    print(f"bench gate OK ({n} keys/s rows within -{THRESHOLD:.0%}, "
          f"{n_slo} p99 rows within +{SLO_THRESHOLD:.0%} of baseline, "
          f"telemetry wave overhead {fresh.get('telemetry_overhead_pct')}% "
          f"<= {TELEMETRY_PCT}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
