"""The readers of the four-chip cell's per-layer metrics: each on a
hand-built reduced trace, then on a window recorded on four v5e chips (the
cell at 2^14 buckets a shard, 4,096-key calls), kept as its reduction."""
import json
import os

import pytest

from bench import costmodel_writes, harness, trace_reduce

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MS = 1_000_000
PEAKS = {"hbm_bytes_per_s": 819e9}
COUNTERS = {"offered": 4000, "deferred": 40, "insert_keys": 1000,
            "delete_keys": 600, "n_shards": 4, "bucket_size": 4,
            "stash_slots": 1024}


def reduced(spans=(), *, programs=None, ops=None, busy=(0.0,), window=1.0):
    return trace_reduce.Reduced(window_s=window, n_devices=len(busy),
                                busy_s=list(busy), programs=programs or {},
                                ops=ops or {}, gaps=[], spans=list(spans))


def read(name, r, counters=COUNTERS):
    return harness.metric_reader(name)({"reduced": r, "counters": counters,
                                        "peaks": PEAKS})


def call(t, kind="insert", dispatch=2, harvest=5):
    """One routed call at ``t`` ms: its dispatch, with the routed program's
    enqueue inside, then its harvest."""
    return [("shard_dispatch", t * MS, (t + dispatch) * MS),
            ("shard_prepare", t * MS, (t + 0.5) * MS),
            (f"distributed.{kind}", (t + 1) * MS, (t + 1.5) * MS),
            ("shard_harvest", (t + 10) * MS, (t + 10 + harvest) * MS),
            ("harvest_wait", (t + 10) * MS, (t + 12) * MS)]


def test_dispatch_and_harvest_medians():
    spans = (call(0, dispatch=1, harvest=4) + call(100, dispatch=2,
                                                   harvest=6)
             + call(200, kind="delete", dispatch=9, harvest=5))
    assert read("shard.dispatch_ms", reduced(spans)) == pytest.approx(2.0)
    assert read("shard.harvest_ms", reduced(spans)) == pytest.approx(5.0)
    assert read("shard.dispatch_ms", reduced()) is None
    assert read("shard.harvest_ms", reduced()) is None


def test_a2a_per_call_mean_over_chips():
    spans = call(0) + call(100, "delete") + call(200, "lookup") \
        + [("pump_resubmit", 300 * MS, 320 * MS),
           ("distributed.insert", 301 * MS, 302 * MS)]
    # Op names as a TPU trace has them: the HLO text.  A consumer of an
    # all-to-all names it as an operand, which does not count.
    ops = {"%all_to_all.1 = u32[4,9] all-to-all(u32[4,9] %a)": 0.004,
           "%all_to_all.7 = pred[4,9] all-to-all(pred[4,9] %b)": 0.012,
           "%fusion.3 = u32[9] fusion(u32[4,9] %all_to_all.1)": 1.0}
    r = reduced(spans, ops=ops, busy=(0.1,) * 4)
    # 16 ms over 4 chips and 4 routed calls, the resubmission among them
    assert read("a2a.ms_per_call", r) == pytest.approx(1.0)
    assert read("a2a.ms_per_call", reduced(spans, busy=(0.1,) * 4)) is None
    assert read("a2a.ms_per_call", reduced(ops=ops, busy=(0.1,) * 4)) is None


@pytest.mark.parametrize("kind", ["insert", "delete"])
def test_routed_write_rooflines(kind):
    spans = call(0, kind) + call(100, kind) + call(200, "lookup")
    t = 0.002
    r = reduced(spans, programs={f"jit_routed_{kind}": t,
                                 "jit_routed_lookup": 5.0})
    nbytes = COUNTERS[f"{kind}_keys"] * 45 + 2 * 4 * 8192
    assert costmodel_writes.write_bytes(
        COUNTERS[f"{kind}_keys"], 2, bucket_size=4, stash_slots=1024,
        n_shards=4) == nbytes
    assert read(f"{kind}_roofline", r) == pytest.approx(
        100 * nbytes / 819e9 / t)
    assert read(f"{kind}_roofline", reduced(spans)) is None
    assert read(f"{kind}_roofline", reduced(programs={
        f"jit_routed_{kind}": t})) is None


def test_idle_share_and_deferred_share():
    r = reduced(busy=(0.25, 0.5, 0.25, 0.2), window=2.0)
    assert read("device.idle_pct.4chip", r) == pytest.approx(85.0)
    assert read("device.idle_pct.4chip", reduced(busy=())) is None
    assert read("routing.deferred_pct", r) == pytest.approx(1.0)
    assert read("routing.deferred_pct", r, {"offered": 0,
                                            "deferred": 0}) is None


def _recorded():
    with open(os.path.join(FIXTURES, "routed_window.reduced.json")) as f:
        d = json.load(f)
    r = reduced([tuple(t) for t in d["spans"]], programs=d["programs"],
                ops=d["ops"], busy=d["busy_s"], window=d["window_s"])
    return r, d["counters"]


def test_recorded_four_chip_window():
    """Six calls of the cell recorded on four v5e chips: the routed programs
    and an all-to-all are found under the names the readers use, the spans
    are there, and both rooflines stay under 100%."""
    r, counters = _recorded()
    assert r.n_devices == 4
    assert all(0 < b < r.window_s for b in r.busy_s)
    for kind in ("insert", "delete", "lookup"):
        assert r.program_s(rf"routed_{kind}\b") > 0
    a2a = [n for n in r.ops if "all-to-all(" in n]
    assert a2a and all(n.startswith("%all_to_all.") for n in a2a)
    assert 0 < read("a2a.ms_per_call", r, counters) < 5
    for kind in ("insert", "delete"):
        assert 0 < read(f"{kind}_roofline", r, counters) <= 100
    assert 0 < read("device.idle_pct.4chip", r, counters) < 100
    assert read("shard.dispatch_ms", r, counters) > 0
    assert read("shard.harvest_ms", r, counters) > 0
    assert read("routing.deferred_pct", r, counters) == 0.0
