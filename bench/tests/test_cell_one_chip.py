"""The one-chip cell rehearsed on the CPU at a small size: set-up, window
and the check against the reference, through the same code as on the chip.
Then the control (fingerprints cut to 12 bits, ``bench/control.py``) and
the faults the cell can have, each planted in the program under the timed
path, must make the run come out not correct.  ``run_cell`` skips only the
look for a chip.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run, sweep
from bench.control import CONTROL

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 17
ONE = "kvfilter-1chip.read_latest"
SMALL_1 = {"n_buckets": 1 << 12, "setup_chunk": 1 << 10, "check_sample": 2048}
MIX_1 = {"rate_per_s": 250.0}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark alone, so traces land outside the repo."""
    r = tmp_path_factory.mktemp("bench_root")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), r)
    shutil.copytree(os.path.join(ROOT, "bench"), r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return str(r)


def one_chip(root, trace=False, config=None):
    # A traced window is short: the CPU profiler records every eager op.
    return run.run_cell(ONE, seed=SEED, seconds=0.04 if trace else 0.4,
                        trace=trace, root=root,
                        require_tpu=False, cache=False,
                        config_override={**SMALL_1, **(config or {})},
                        mix_override=MIX_1)


def test_one_chip_cell(root):
    res = one_chip(root)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 100
    assert set(res["metrics"]) == {"lat_p50_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"unanswered", "false_negatives", "fpr",
                                  "lost_writes", "occupancy_gap"}
    assert res["device"]["count"] == 1


def test_one_chip_traced(root):
    res = one_chip(root, trace=True)
    assert res["correct"]
    assert "batcher.dispatch_ms" in res["metrics"]
    assert res["device"]["window_s"] > 0.03
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_one_chip_control_fails(root):
    res = one_chip(root, config=CONTROL)
    assert not res["correct"]
    fpr = res["checks"]["fpr"]
    assert fpr["value"] > fpr["limit"]
    assert res["checks"]["false_negatives"]["value"] == 0


def _fault_state_unchanged(monkeypatch):
    from repro.core.filter_ops import FilterOps

    def insert_spill(self, state, stash, hi, lo, valid=None):
        return state, stash, valid            # acknowledged, never stored
    monkeypatch.setattr(FilterOps, "insert_spill", insert_spill)


def _fault_half_batch(monkeypatch):
    from repro.core.filter_ops import FilterOps
    real = FilterOps.lookup_with_stash

    def lookup(self, state, stash, hi, lo):
        n = hi.shape[0] // 2                  # the second half left out
        return real(self, state, stash, hi, lo).at[n:].set(False)
    monkeypatch.setattr(FilterOps, "lookup_with_stash", lookup)


def _fault_answer_altered(monkeypatch):
    from repro.core.filter_ops import FilterOps
    real = FilterOps.lookup_with_stash

    def lookup(self, state, stash, hi, lo):
        hit = real(self, state, stash, hi, lo)
        return hit.at[0].set(~hit[0])         # one answer flipped
    monkeypatch.setattr(FilterOps, "lookup_with_stash", lookup)


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch,
                                   _fault_answer_altered])
def test_one_chip_faults_fail(root, monkeypatch, fault):
    fault(monkeypatch)
    res = one_chip(root)
    assert not res["correct"], res["checks"]


def test_run_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, os.path.join(ROOT, "bench",
                                                       "run.py"),
                          "--workload", ONE, "--seed", "1", "--seconds", "1",
                          "--trace", "0"], capture_output=True, text=True,
                         timeout=300, env=env, cwd=ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr


def test_run_refuses_without_the_program(root):
    """A checkout holding only BENCHMARK.json and bench/ runs nothing."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "bench/run.py", "--workload", ONE,
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=root)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_seeds_change_the_keys_not_the_work(root):
    a = one_chip(root)
    b = run.run_cell(ONE, seed=SEED + 1, seconds=0.4, trace=False, root=root,
                     require_tpu=False, cache=False,
                     config_override=SMALL_1, mix_override=MIX_1)
    assert a["attempted"] == b["attempted"]
    assert b["correct"]
    assert np.isfinite(b["metrics"]["lat_p50_ms"]["value"])


def test_sweep_runs_the_timed_path_per_rate(root, capsys):
    """The sweep is whole runs of the cell, one per rate, lowest first."""
    rc = sweep.main(["--workload", ONE, "--seed", str(SEED), "--seconds",
                     "0.2", "--rates", "300", "100"],
                    root=root, require_tpu=False, cache=False,
                    config_override=SMALL_1)
    assert rc == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [r["rate_per_s"] for r in rows] == [100.0, 300.0]
    assert [r["requests"] for r in rows] == [20, 60]
    assert all(r["correct"] and r["trend"] > 0 for r in rows)
