"""CPU rehearsal of ``chip_smoke.py``: its phases at small sizes.

The one-chip phases run here at 2^12 buckets; the four-chip routed phase
runs on four virtual CPU devices in a subprocess (the device count must be
forced before jax starts).  Only ``main()`` refuses a host without a TPU.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _quiet(*_args):
    pass


def test_keys_distinct_and_disjoint():
    loaded = chip_smoke.loaded_keys(3, 0, 1 << 16)
    absent = chip_smoke.absent_keys(3, 1 << 16)
    assert np.unique(loaded).size == loaded.size
    assert not np.isin(absent, loaded).any()
    # top bit set: disjoint from the serving scenarios' keys (< 2^63)
    assert (loaded >= np.uint64(1 << 63)).all()
    assert np.array_equal(chip_smoke.keys_at(3, np.arange(5, 9)),
                          loaded[5:9])


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_single_chip_phases(backend):
    """Load to 0.85, serve the three scenarios, check against the exact
    reference — ``pallas`` runs the kernel arm in the form the chip runs
    (the XLA emulation), ``auto`` the CPU default."""
    res = chip_smoke.single_chip(n_buckets=1 << 12, seed=1, waves=12,
                                 n_absent=1 << 16, batch=1024,
                                 backend=backend, log=_quiet)
    assert res["false_negatives"] == 0
    assert res["fpr"] <= res["fpr_bound"]
    assert res["table_slots"] + res["stash_slots"] == \
        res["expected_occupancy"]
    assert res["served_inserts"] > 0 and res["served_deletes"] > 0
    assert res["ok"], res


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


SHARDED = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, %r)
    import chip_smoke
    res = chip_smoke.sharded_phase(n_shards=4, n_buckets=1024, seed=2,
                                   batch=1024, n_absent=1 << 15,
                                   backend="pallas", log=lambda *a: None)
    print(json.dumps(res))
""")


def test_sharded_phase_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED % REPO],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["false_negatives"] == 0
    assert res["inserts_acked"] == res["n_keys"]
    assert res["load"] > 0.8
    assert res["resubmitted_lanes"] > 0         # the router deferred lanes
    assert res["shard_occupancy"] == res["owned"]
    assert res["ok"], res
