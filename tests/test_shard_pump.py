"""The served entry point of a sharded filter (``DeferredWritePump.call``)
on four virtual CPU devices, held to an exact key set.

Lookups, inserts and deletes of 64-bit keys at 2^12 buckets a shard, with a
write routing capacity of one fair share, so that writes are deferred: parked
lanes are replayed in submission order (a delete issued right behind the
insert of the same keys clears them all), every acknowledged write is applied once
``run_until_drained`` returns, no live key answers absent, and the tables
plus stashes hold one entry per live key.  The spans and counters the pump
records are checked too.  The mesh runs in a subprocess so the forced
device count does not leak into other tests.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.tier1

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    from repro.core import distributed as dist
    from repro.core import hashing
    from repro.launch.mesh import make_mesh
    from repro.obs import MetricsRegistry, TraceRecorder
    from repro.serving.scheduler import DeferredWritePump

    mesh = make_mesh((4,), ("data",))
    reg, tr = MetricsRegistry(), TraceRecorder()
    pump = DeferredWritePump(
        mesh, "data", dist.make_sharded_state(4, 1 << 12, 4, stash_slots=64),
        fp_bits=16, capacity_factor=1.0, metrics=reg, tracer=tr)
    rng = np.random.default_rng(5)
    keys = np.unique(rng.integers(1, 2**63, 9000, dtype=np.int64)
                     ).astype(np.uint64)[:8192]
    a, b, c, absent = np.split(keys, 4)          # 2,048 keys each
    writes = []

    def write(kind, ks):
        writes.append(pump.call(kind, ks))

    # A delete right behind the insert of the same keys, before any drain:
    # it must wait for the insert's parked lanes.
    write("insert", a)
    write("delete", a)
    write("insert", b)
    pump.run_until_drained()
    acked = all(w.results is not None and w.results.all() for w in writes)
    first_deferred = {w.kind: int(w.deferred.sum()) for w in writes[:2]}

    # While held, a write parks whole and a lookup still answers.
    pump.hold()
    held = pump.call("insert", c)
    look = pump.call("lookup", b)
    pump.flush()
    held_parked = bool(held.deferred.all()) and held.results is None
    held_lookup_fn = int((~look.results).sum())
    pump.release()
    pump.run_until_drained()
    held_applied = held.results is not None and bool(held.results.all())
    live = set(b.tolist()) | set(c.tolist())

    def lookup_all():
        calls = [pump.call("lookup", ks) for ks in (a, b, c, absent)]
        pump.flush()
        return np.concatenate([x.results for x in calls])

    exact = lookup_all()
    everything = np.concatenate([a, b, c, absent])
    is_live = np.array([int(k) in live for k in everything])
    # Keys all owned by one shard: every source slice sends its whole share
    # there, twice the lookup's routing capacity, so lanes overflow.
    hi, lo = hashing.key_to_u32_pair_np(everything)
    one = hashing.owner_shard_np(hi, lo, 4) == 0
    skew = pump.call("lookup", everything[one][:2048])
    pump.flush()
    skew_live = is_live[one][:2048]
    st = pump.state
    held_slots = int(np.asarray((st.tables != 0).sum())
                     + np.asarray((st.stashes[:, 0, :] != 0).sum()))
    spans = {}
    for e in tr.events:
        if e.get("ph") == "X":
            spans.setdefault(e["name"], e["args"])
    snap = reg.snapshot()
    print(json.dumps({
        "acked": acked, "first_deferred": first_deferred,
        "held_parked": held_parked, "held_lookup_fn": held_lookup_fn,
        "held_applied": held_applied,
        "fn_skewed": int((skew_live & ~skew.results).sum()),
        "skew_dead": int((~skew_live).sum()),
        "skew_dead_present": int((~skew_live & skew.results).sum()),
        "fn": int((is_live & ~exact).sum()),
        "dead_present": int((~is_live & exact).sum()),
        "dead": int((~is_live).sum()),
        "held_slots": held_slots, "live": len(live), "pending": pump.pending,
        "lanes": {f"{w}.{k}": v for (w, k), v in pump.stats.lanes.items()},
        "spans": spans,
        "counters": sorted(k for k in snap if k.startswith("routing_")),
    }))
""")


@pytest.fixture(scope="module")
def res():
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_writes_deferred_then_replayed_in_order(res):
    assert res["first_deferred"]["insert"] > 0
    assert res["first_deferred"]["delete"] > 0
    assert res["acked"], "every write of a live key acknowledged"
    assert res["pending"] == 0
    lanes = res["lanes"]
    for kind in ("insert", "delete"):
        assert lanes[f"resubmitted.{kind}"] > 0
        assert lanes.get(f"failed.{kind}", 0) == 0


def test_exact_membership_and_occupancy(res):
    assert res["fn"] == 0 and res["fn_skewed"] == 0
    assert res["dead_present"] <= res["dead"] // 100
    assert res["held_slots"] == res["live"]


def test_hold_parks_writes_not_lookups(res):
    assert res["held_parked"] and res["held_applied"]
    assert res["held_lookup_fn"] == 0


def test_overflowed_lookups_answer_maybe_and_are_counted(res):
    assert res["lanes"]["overflowed.lookup"] > 0
    # About half the skewed call overflowed: its dead keys answer "maybe".
    assert res["skew_dead_present"] >= res["skew_dead"] // 4 > 0


def test_spans_and_counters(res):
    spans = res["spans"]
    for name in ("shard_dispatch", "shard_prepare", "shard_upload",
                 "distributed.insert", "distributed.delete",
                 "distributed.lookup", "shard_harvest", "harvest_wait",
                 "harvest_fetch", "pump_resubmit"):
        assert set(spans[name]) == {"call", "kind", "n"}, name
    for what in ("offered", "deferred", "resubmitted", "overflowed",
                 "acked"):
        assert any(c.startswith(f"routing_{what}_lanes{{kind=")
                   for c in res["counters"]), what
