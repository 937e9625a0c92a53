"""Find the highest request rate an open-loop cell sustains.

    python3 bench/sweep.py --workload kvfilter-1chip.read_latest --seed 7 \\
        --seconds 10 --rates 100 200 300 400

Runs the cell once per rate, lowest first, each a whole run of the timed
path (``bench/run.py``'s ``measure``: set-up, warm-up, window and check)
with only the mix's ``rate_per_s`` changed, and prints one JSON line per
rate: whether it was correct, the latency percentiles, how late the
generator ran, and the backlog trend (``bench.openloop.trend``).  A rate is
sustained while the trend stays near 1 and the generator keeps to its
schedule; the knee is the highest such rate.  The cell's mix then offers a
fixed share of it.  The benchmark's own runs never call this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import harness, run  # noqa: E402

SHOWN = ("lat_p95_ms", "lat_max_ms", "late_p99_ms", "trend", "requests")


def main(argv=None, **kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    for k, rate in enumerate(sorted(args.rates)):
        try:
            _cell, _ctx, out, _lv, _r = run.measure(
                args.workload, seed=args.seed + k, seconds=args.seconds,
                trace=False, t_start=T_START if k == 0 else time.perf_counter(),
                mix_override={"rate_per_s": rate}, **kw)
        except harness.NoChip as e:
            harness.stderr(f"sweep: {e}")
            return 2
        print(json.dumps({"rate_per_s": rate,
                          "correct": harness.is_correct(out.checks),
                          **out.metrics,
                          **{k2: out.info[k2] for k2 in SHOWN}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
