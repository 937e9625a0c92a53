"""The control: a run of a cell that must come out not correct.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

The configurations state 16-bit fingerprints and a false-positive rate of
at most 4 x 2b / 2^f.  The program can store shorter fingerprints; the
control runs the whole cell with ``fp_bits`` 12 (set-up, window and check
alike), which breaks that stated rate by about 3.4 times, and prints the
result line: its ``fpr`` check must fail.  The benchmark's own runs never
call this; ``bench/tests/test_cells_cpu.py`` runs it at a small size.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench.run import parse, run_cell  # noqa: E402

CONTROL = {"fp_bits": 12}


def main(argv=None) -> int:
    args = parse(argv)
    res = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=T_START,
                   config_override=CONTROL)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
