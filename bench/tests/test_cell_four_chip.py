"""The four-chip cell rehearsed on four virtual CPU devices at 2^12 buckets
a shard: set-up, window and check through the same code as on the chip,
untraced and traced, and with writes deferred by a small routing capacity.
Then the control (fingerprints cut to 12 bits, ``bench/control.py``) and
three faults planted in the program under the timed path, each of which
must make the run come out not correct.  The device count is forced before
JAX starts, so every run is made in one subprocess, whose results the tests
read.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = r'''
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root, src = sys.argv[1], sys.argv[2]
sys.path[:0] = [root, src]
import jax.numpy as jnp
import numpy as np
from bench import run
from bench.control import CONTROL
from repro.core import distributed as dist
from repro.core import hashing
from repro.core.filter_ops import FilterOps
from repro.serving.scheduler import DeferredWritePump

CELL = "kvfilter-4shard.ttl_churn"
SMALL = {"n_buckets": 1 << 12, "setup_chunk": 1 << 10, "check_sample": 8192}
MIX = {"keys_per_call": 256}
TIGHT = {"capacity_factor": 1.0}       # routing defers writes' lanes
real_delete = FilterOps.delete_table
real_book = DeferredWritePump._book_write


def noop_delete(self, table, hi, lo, *, n_buckets=None, valid=None,
                stash=None):
    return table, stash, valid                 # acknowledged, never cleared


def foreign_delete(self, table, hi, lo, *, n_buckets=None, valid=None,
                   stash=None):
    table, stash, ok = real_delete(self, table, hi, lo, n_buckets=n_buckets,
                                   valid=valid, stash=stash)
    # ... and the last occupied slot of the first lane's first bucket, most
    # often another key's fingerprint, cleared too.
    b = hashing.index_hash(hi[:1], lo[:1], table.shape[0])[0]
    slot = jnp.argmax(jnp.arange(table.shape[1]) * (table[b] != 0))
    return table.at[b, slot].set(0), stash, ok


def dropped_deferred_delete(self, att, ok, deferred):
    if att.kind == "delete":                   # parked lanes acknowledged
        ok, deferred = ok | deferred, np.zeros_like(deferred)
    return real_book(self, att, ok, deferred)


def measure(trace=False, seconds=0.4, config=None, patches=()):
    for cls, name, fn in patches:
        setattr(cls, name, fn)
    traced_op = any(cls is FilterOps for cls, _n, _f in patches)
    if traced_op:                  # the routed programs trace it anew
        dist._routed_write_fn.cache_clear()
    try:
        _cell, _ctx, out, layer, reduced = run.measure(
            CELL, seed=2 ** 31 + 17, seconds=seconds, trace=trace, root=root,
            require_tpu=False, cache=False,
            config_override={**SMALL, **(config or {})},
            mix_override=MIX)
        line = run.result_line(_cell, _ctx, out, layer_values=layer,
                               reduced=reduced)
        return {"line": line, "counters": out.counters}
    finally:
        DeferredWritePump._book_write = real_book
        FilterOps.delete_table = real_delete
        if traced_op:
            dist._routed_write_fn.cache_clear()


out = {
    "sound": measure(),
    "traced": measure(trace=True, seconds=0.1),
    "deferring": measure(config=TIGHT),
    "control": measure(config=CONTROL),
    "noop_delete": measure(patches=[(FilterOps, "delete_table",
                                     noop_delete)]),
    "foreign_delete": measure(patches=[(FilterOps, "delete_table",
                                        foreign_delete)]),
    "dropped_deferred_delete": measure(
        config=TIGHT, patches=[(DeferredWritePump, "_book_write",
                                dropped_deferred_delete)]),
}
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every run of the cell, in one subprocess on four CPU devices, from a
    checkout of the benchmark alone, so traces land outside the repo."""
    r = tmp_path_factory.mktemp("bench_root")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), r)
    shutil.copytree(os.path.join(ROOT, "bench"), r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(r),
                          os.path.join(ROOT, "src")],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


CHECKS = {"unanswered", "false_negatives", "fpr", "lost_writes",
          "occupancy_gap", "delete_misses", "blind_deletes"}


def test_four_chip_cell(runs):
    res = runs["sound"]["line"]
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"lat_p50_ms", "setup_s"}
    assert set(res["checks"]) == CHECKS
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 4
    c = runs["sound"]["counters"]
    assert c["insert_keys"] > 0 and c["delete_keys"] > 0
    assert c["deferred"] == 0


def test_four_chip_traced(runs):
    res = runs["traced"]["line"]
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["shard.dispatch_ms"]["value"] > 0
    assert m["shard.harvest_ms"]["value"] > 0
    assert m["routing.deferred_pct"]["value"] == 0.0
    assert res["device"]["window_s"] > 0.05


def test_four_chip_deferred_writes_replayed(runs):
    """Writes deferred by routing overflow are parked and replayed in order,
    and the run still holds to every limit."""
    res, c = runs["deferring"]["line"], runs["deferring"]["counters"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert c["deferred"] > 0


def test_four_chip_control_fails(runs):
    res = runs["control"]["line"]
    assert not res["correct"]
    fpr = res["checks"]["fpr"]
    assert fpr["value"] > fpr["limit"]
    assert res["checks"]["false_negatives"]["value"] == 0


@pytest.mark.parametrize("fault,broken", [
    ("noop_delete", ("fpr", "occupancy_gap")),
    ("foreign_delete", ("occupancy_gap",)),
    ("dropped_deferred_delete", ("fpr", "occupancy_gap")),
])
def test_four_chip_faults_fail(runs, fault, broken):
    res = runs[fault]["line"]
    assert not res["correct"], res["checks"]
    for name in broken:
        assert res["checks"][name]["value"] > res["checks"][name]["limit"]
    if fault == "foreign_delete":         # a member's entry is gone
        assert (res["checks"]["false_negatives"]["value"]
                + res["checks"]["lost_writes"]["value"]) > 0
