"""Observability tests — counter-plane parity, registry, spans, policy.

The contracts pinned here:

  * **dispatch identity**: a batcher with telemetry OFF issues exactly
    the pre-telemetry device-call sequence (no entry point called with
    ``telemetry=True``), and its results and final filter state are
    bit-for-bit those of a batcher built without any observability
    kwargs at all —
    attaching a registry or tracer must not change the device work;
  * **telemetry parity**: turning the counter planes ON changes the
    counters, never the answers — results, tables, stashes and counts
    stay bit-identical to the off path, while the registry fills with a
    kick-depth histogram whose mass equals the insert lanes offered;
  * **trip -> shed -> readmit**: the registry-fed ``BackpressureController``
    walks the admit/defer/shed state machine off the same metrics the
    admission gate publishes, with hysteresis on the way back down;
  * **vectorized ground truth**: ``measure_false_positives`` /
    ``measure_false_negatives`` through the batch keystore pass agree
    with the per-key scalar loop they replaced;
  * merge associativity of the device telemetry fold (hypothesis,
    optional dep — not tier-1).
"""
import json

import numpy as np
import pytest

import repro.kernels.ops as kops_mod
from repro.core import filter as jfilter
from repro.core.filter_ops import FilterOps
from repro.core.keystore import VectorKeystore
from repro.core.metrics import (measure_false_negatives,
                                measure_false_positives)
from repro.core.ocf import OCF, OcfConfig
from repro.kernels import ops as kops
from repro.obs import MetricsRegistry, TraceRecorder
from repro.serving.engine import BackpressureConfig, BackpressureController
from repro.serving import scheduler
from repro.serving.scheduler import FilterOpBatcher
from repro.streaming.admission import AdmissionConfig, AdmissionController

pytestmark = pytest.mark.obs

WS = 64

# every device entry point the batcher can reach, off and on
_SPIED = ("probe_dispatch", "filter_insert", "filter_delete",
          "adaptive_lookup", "adaptive_insert", "adaptive_delete",
          "adaptive_report")


def _spy_kops(monkeypatch):
    """Record (name, telemetry) of every kops entry point the batcher's
    wave programs call.  The entry points run while a program is traced,
    so ``_mk_batcher`` drops the cached programs and each batcher traces
    its own."""
    calls = []

    def wrap(name):
        orig = getattr(kops_mod, name)

        def wrapped(*a, **k):
            calls.append((name, k.get("telemetry", False)))
            return orig(*a, **k)

        return wrapped

    for name in _SPIED:
        monkeypatch.setattr(kops_mod, name, wrap(name))
    return calls


def _mk_batcher(**obs_kwargs):
    scheduler._wave_program.cache_clear()
    ops = FilterOps(backend="pallas", evict_rounds=16)
    state = jfilter.make_state(256, buffer_buckets=256)
    stash = kops.make_stash(16)
    return FilterOpBatcher(ops, state, stash=stash, wave_slots=WS,
                           double_buffer=True, **obs_kwargs)


def _replay(batcher, rng):
    results = []
    for i in range(6):
        kind = ("insert", "lookup", "delete")[i % 3]
        keys = rng.randint(1, 2 ** 62, size=WS, dtype=np.int64)
        wave = batcher.submit(kind, keys.astype(np.uint64))
        results.append(wave)
    batcher.flush()
    return [w.results for w in results]


@pytest.mark.tier1
def test_telemetry_off_dispatch_identical(monkeypatch):
    """Attaching metrics/tracer with telemetry OFF must not change the
    device-call sequence or any bit of the results/state."""
    import jax.numpy as jnp

    calls = _spy_kops(monkeypatch)
    plain = _mk_batcher()
    res_plain = _replay(plain, np.random.RandomState(3))
    seq_plain = list(calls)

    calls.clear()
    observed = _mk_batcher(metrics=MetricsRegistry(), tracer=TraceRecorder())
    res_obs = _replay(observed, np.random.RandomState(3))
    seq_obs = list(calls)

    assert seq_obs == seq_plain
    assert seq_obs and not any(tm for _name, tm in seq_obs)
    for a, b in zip(res_plain, res_obs):
        np.testing.assert_array_equal(a, b)
    assert jnp.array_equal(plain.state.table, observed.state.table)
    assert jnp.array_equal(plain.stash, observed.stash)
    assert int(plain.state.count) == int(observed.state.count)


@pytest.mark.tier1
def test_telemetry_on_counters_change_answers_dont(monkeypatch):
    import jax.numpy as jnp

    calls = _spy_kops(monkeypatch)
    plain = _mk_batcher()
    res_plain = _replay(plain, np.random.RandomState(5))

    calls.clear()
    m = MetricsRegistry()
    on = _mk_batcher(telemetry=True, metrics=m)
    res_on = _replay(on, np.random.RandomState(5))

    # the telemetry arm asks EVERY entry point for its counter plane
    assert calls and all(tm for _name, tm in calls)
    for a, b in zip(res_plain, res_on):
        np.testing.assert_array_equal(a, b)
    assert jnp.array_equal(plain.state.table, on.state.table)
    assert jnp.array_equal(plain.stash, on.stash)
    assert int(plain.state.count) == int(on.state.count)

    snap = m.snapshot()
    kick = snap["filter_kick_depth"]
    assert sum(kick["counts"]) == 2 * WS  # every insert lane binned once
    assert 'filter_waves{kind="insert"}' in snap
    assert any(k.startswith("filter_probe_depth") for k in snap)
    assert "filter_stash_fill_hw" in snap
    assert len(m.ring) == 6


@pytest.mark.tier1
def test_adaptive_telemetry_parity():
    import jax.numpy as jnp

    from repro.adaptive.state import make_adaptive_state

    def mk(**kw):
        return FilterOpBatcher(FilterOps(backend="pallas", evict_rounds=16),
                               make_adaptive_state(256),
                               stash=kops.make_stash(8), wave_slots=WS,
                               double_buffer=True, **kw)

    rng = np.random.RandomState(11)
    keys = rng.randint(1, 2 ** 62, size=WS, dtype=np.int64).astype(np.uint64)
    m = MetricsRegistry()
    on, off = mk(telemetry=True, metrics=m), mk()
    for b in (on, off):
        b.submit("insert", keys)
        b.submit("lookup", keys)
        b.submit("report", keys[:16])
        b.submit("delete", keys[:32])
        b.flush()
    assert jnp.array_equal(on.state.table, off.state.table)
    assert jnp.array_equal(on.state.sels, off.state.sels)
    assert jnp.array_equal(on.stash, off.stash)
    assert int(on.state.count) == int(off.state.count)
    snap = m.snapshot()
    # every inserted key was present: lookups must all land at some depth
    depth = sum(v for k, v in snap.items()
                if k.startswith("filter_probe_depth"))
    assert depth == WS
    assert snap.get("filter_table_deletes", 0) + snap.get(
        "filter_stash_deletes", 0) >= 1


def _pair(rng, n):
    import jax.numpy as jnp

    from repro.core import hashing
    keys = rng.randint(1, 2 ** 62, size=n, dtype=np.int64).astype(np.uint64)
    hi, lo = hashing.key_to_u32_pair_np(keys)
    return jnp.asarray(hi), jnp.asarray(lo)


@pytest.fixture(scope="module")
def tm_world():
    """Static and adaptive filters at load ~0.8 of 64 buckets with a part
    filled stash, the keys they hold and 40 they do not, and a batch of
    the adaptive filter's false positives."""
    from repro.adaptive.state import make_adaptive_state

    rng = np.random.RandomState(21)
    hi, lo = _pair(rng, 240)
    ops = FilterOps(backend="pallas", evict_rounds=8)
    static, static_stash, _ = ops.insert_spill(
        jfilter.make_state(64, buffer_buckets=64), kops.make_stash(16),
        hi[:200], lo[:200])
    aops = FilterOps(fp_bits=8, backend="pallas", evict_rounds=8)
    adaptive, adaptive_stash, _ = aops.insert_adaptive(
        make_adaptive_state(64), hi[:200], lo[:200],
        stash=kops.make_stash(16))
    phi, plo = _pair(rng, 4096)
    fp = np.asarray(aops.lookup_adaptive(adaptive, phi, plo))
    return dict(ops=ops, static=static, static_stash=static_stash,
                aops=aops, adaptive=adaptive, adaptive_stash=adaptive_stash,
                hi=hi, lo=lo, fp_hi=phi[fp], fp_lo=plo[fp])


# entry -> (kind of counters it fills, call(world, telemetry))
_TM_ENTRIES = {
    "lookup": ("lookup", lambda w, t: w["ops"].lookup(
        w["static"], w["hi"], w["lo"], telemetry=t)),
    "lookup_with_stash": ("lookup", lambda w, t: w["ops"].lookup_with_stash(
        w["static"], w["static_stash"], w["hi"], w["lo"], telemetry=t)),
    "insert": ("insert", lambda w, t: w["ops"].insert(
        w["static"], w["hi"][200:], w["lo"][200:], telemetry=t)),
    "insert_spill": ("insert", lambda w, t: w["ops"].insert_spill(
        w["static"], w["static_stash"], w["hi"][200:], w["lo"][200:],
        telemetry=t)),
    "delete": ("delete", lambda w, t: w["ops"].delete(
        w["static"], w["hi"][:100], w["lo"][:100], telemetry=t)),
    "delete_table": ("delete", lambda w, t: w["ops"].delete_table(
        w["static"].table, w["hi"][:200], w["lo"][:200],
        n_buckets=w["static"].n_buckets, stash=w["static_stash"],
        telemetry=t)),
    "lookup_adaptive": ("lookup", lambda w, t: w["aops"].lookup_adaptive(
        w["adaptive"], w["hi"], w["lo"], telemetry=t)),
    "lookup_adaptive_stash": ("lookup", lambda w, t:
                              w["aops"].lookup_adaptive(
                                  w["adaptive"], w["hi"], w["lo"],
                                  stash=w["adaptive_stash"], telemetry=t)),
    "insert_adaptive": ("insert", lambda w, t: w["aops"].insert_adaptive(
        w["adaptive"], w["hi"][200:], w["lo"][200:], telemetry=t)),
    "insert_adaptive_stash": ("insert", lambda w, t:
                              w["aops"].insert_adaptive(
                                  w["adaptive"], w["hi"][200:],
                                  w["lo"][200:], stash=w["adaptive_stash"],
                                  telemetry=t)),
    "delete_adaptive": ("delete", lambda w, t: w["aops"].delete_adaptive(
        w["adaptive"], w["hi"][:100], w["lo"][:100], telemetry=t)),
    "delete_adaptive_stash": ("delete", lambda w, t:
                              w["aops"].delete_adaptive(
                                  w["adaptive"], w["hi"][:200],
                                  w["lo"][:200], stash=w["adaptive_stash"],
                                  telemetry=t)),
    "report_false_positive": ("report", lambda w, t:
                              w["aops"].report_false_positive(
                                  w["adaptive"], w["fp_hi"], w["fp_lo"],
                                  telemetry=t)),
}


@pytest.mark.tier1
@pytest.mark.parametrize("entry", list(_TM_ENTRIES))
def test_telemetry_arg_matches_plain_entry(tm_world, entry):
    """``telemetry=True`` returns the plain entry's answers, tables, stash
    and count bit for bit, plus a ``FilterTelemetry`` whose counters
    account for the batch."""
    import jax

    kind, call = _TM_ENTRIES[entry]
    off = call(tm_world, False)
    off = off if isinstance(off, tuple) else (off,)
    *on, tm = call(tm_world, True)
    assert isinstance(tm, kops.FilterTelemetry)
    leaves_off = jax.tree_util.tree_leaves(off)
    leaves_on = jax.tree_util.tree_leaves(tuple(on))
    assert len(leaves_on) == len(leaves_off)
    for a, b in zip(leaves_off, leaves_on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if kind == "lookup":
        # every lane lands at one depth; the 40 absent keys mostly miss
        assert int(tm.probe_depth.sum()) == 240
        assert int(tm.probe_depth[3]) > 0
    elif kind == "insert":
        assert int(tm.kick_hist.sum()) == 40   # every lane binned once
    elif kind == "delete":
        deleted = int(np.asarray(off[-1]).sum())
        assert deleted > 0
        assert int(tm.table_deletes) + int(tm.stash_deletes) == deleted
    else:
        adapted = int(np.asarray(off[1]).sum())
        assert adapted > 0
        assert int(tm.selector_bumps) == adapted


_PARENT_INSERT_STATICS = ("fp_bits", "evict_rounds", "block", "interpret",
                          "emulate", "schedule")


def _lowered_insert(monkeypatch, jit_name, impl, entry, *args, **kwargs):
    """(HLO of the jit ``entry`` dispatches to, with the arguments it
    passes; HLO of ``impl`` jitted with the parent's static argnames)."""
    import jax

    from repro.kernels import insert as kinsert

    orig = getattr(kinsert, jit_name)
    seen = {}
    monkeypatch.setattr(kinsert, jit_name,
                        lambda *a, **k: seen.update(a=a, k=k))
    entry(*args, **kwargs)
    a, k = seen["a"], seen["k"]
    assert k.pop("telemetry") is False
    parent = jax.jit(impl, static_argnames=_PARENT_INSERT_STATICS)
    return (orig.lower(*a, telemetry=False, **k).as_text(),
            parent.lower(*a, **k).as_text())


@pytest.mark.tier1
@pytest.mark.parametrize("stashed", [False, True])
@pytest.mark.parametrize("entry", ["insert_bulk", "insert_bulk_adaptive",
                                   "probe_emulated",
                                   "probe_adaptive_emulated"])
def test_telemetry_off_program_is_the_parents(monkeypatch, entry, stashed):
    """With ``telemetry=False`` each kernel entry lowers to the program its
    telemetry-free jit lowered to: the body jitted with the static
    argnames it had before ``telemetry`` was one of them."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import insert as kinsert
    from repro.kernels.probe import (_probe_adaptive_body, _probe_body,
                                     probe_adaptive_emulated, probe_emulated)
    from repro.kernels.selector import make_key_planes, make_sel_plane

    hi, lo = _pair(np.random.RandomState(2), 256)
    table = jnp.zeros((64, 4), jnp.uint32)
    sels = make_sel_plane(64)
    khi, klo = make_key_planes(64, 4)
    stash = kops.make_stash(16) if stashed else None
    n = jnp.asarray(64, jnp.int32)
    kw = dict(fp_bits=16, valid=jnp.ones(256, bool), evict_rounds=8,
              stash=stash, block=128, interpret=False, emulate=True,
              schedule=True)
    if entry == "insert_bulk":
        new, old = _lowered_insert(monkeypatch, "_insert_bulk_jit",
                                   kinsert._insert_bulk_impl,
                                   kinsert.insert_bulk, table, hi, lo, **kw)
    elif entry == "insert_bulk_adaptive":
        new, old = _lowered_insert(monkeypatch, "_insert_adaptive_jit",
                                   kinsert._insert_adaptive_impl,
                                   kinsert.insert_bulk_adaptive, table,
                                   sels, khi, klo, hi, lo, **kw)
    else:
        # the parent's jits, body for body, under the same names
        def parent_probe_emulated(table, hi, lo, n_buckets, stash, *,
                                  fp_bits):
            return _probe_body(table, stash, hi, lo, n_buckets,
                               fp_bits=fp_bits, array_table=True)

        def parent_probe_adaptive_emulated(table, sels, hi, lo, n_buckets,
                                           stash, *, fp_bits):
            return _probe_adaptive_body(table, sels, stash, hi, lo,
                                        n_buckets, fp_bits=fp_bits,
                                        array_table=True)

        if entry == "probe_emulated":
            fn, body, args = (probe_emulated, parent_probe_emulated,
                              (table, hi, lo, n, stash))
        else:
            fn, body, args = (probe_adaptive_emulated,
                              parent_probe_adaptive_emulated,
                              (table, sels, hi, lo, n, stash))
        body.__name__ = entry
        new = fn.lower(*args, fp_bits=16).as_text()
        assert fn.lower(*args, fp_bits=16,
                        telemetry=False).as_text() == new
        old = jax.jit(body, static_argnames=("fp_bits",)).lower(
            *args, fp_bits=16).as_text()
    assert new == old


@pytest.mark.tier1
@pytest.mark.parametrize("adaptive", [False, True])
def test_lookup_waves_read_no_fill_snapshot(monkeypatch, adaptive):
    """A run of lookup waves computes no fill snapshot (no occupancy
    program, no eager ``stash_occupancy``) and fetches none at harvest;
    ``fill_reads`` and its registry counter count the mutating waves."""
    from repro.adaptive.state import make_adaptive_state

    calls = []

    def spy(name, orig):
        def wrapped(*a, **k):
            calls.append(name)
            return orig(*a, **k)
        return wrapped

    for name in ("_fill_read", "_table_delete_fill"):
        monkeypatch.setattr(scheduler, name,
                            spy(name, getattr(scheduler, name)))
    monkeypatch.setattr(kops_mod, "stash_occupancy",
                        spy("stash_occupancy", kops_mod.stash_occupancy))
    monkeypatch.setattr(FilterOpBatcher, "_take_fills",
                        spy("_take_fills", FilterOpBatcher._take_fills))

    m = MetricsRegistry()
    state = make_adaptive_state(256) if adaptive else jfilter.make_state(256)
    b = FilterOpBatcher(FilterOps(backend="pallas", evict_rounds=16), state,
                        stash=kops.make_stash(16), wave_slots=WS,
                        double_buffer=True, metrics=m)
    rng = np.random.RandomState(2)
    keys = rng.randint(1, 2 ** 62, size=WS, dtype=np.int64).astype(np.uint64)
    b.submit("insert", keys)
    b.flush()
    calls.clear()
    for i in range(5):
        b.submit("lookup", np.roll(keys, i))
    b.flush()
    assert calls == []
    assert b.stats.fill_reads == 1

    b.submit("delete", keys[:WS // 2])
    b.submit("lookup", keys)
    b.submit("insert", keys[:WS // 2])
    b.flush()
    read = "_fill_read" if adaptive else "_table_delete_fill"
    assert [c for c in calls if c != "stash_occupancy"] == [
        read, "_take_fills", "_fill_read", "_take_fills"]
    assert b.stats.fill_reads == 3 and b.stats.waves == 9
    snap = m.snapshot()
    assert snap['filter_fill_reads{kind="insert"}'] == 2
    assert snap['filter_fill_reads{kind="delete"}'] == 1
    assert 'filter_fill_reads{kind="lookup"}' not in snap


@pytest.mark.tier1
def test_backpressure_trip_shed_readmit_sequence():
    """The engine's admit -> defer -> shed -> admit walk over registry
    metrics, exactly as the admission arm publishes them."""
    m = MetricsRegistry()
    bp = BackpressureController(m, BackpressureConfig(defer_signal=0.8,
                                                      resume_signal=0.5))
    sig = m.gauge("admission_signal")

    sig.set(0.1)
    assert bp.decide() == "admit"
    # congestion crosses the defer threshold (the gate trips)
    sig.set(0.9)
    m.counter("admission_trips").inc()
    m.counter("filter_deferred_waves").inc()
    assert bp.decide() == "defer"
    # inside the hysteresis band: still deferring, no flap
    sig.set(0.7)
    assert bp.decide() == "defer"
    # a drain gave up -> genuine shed load escalates
    m.counter("filter_shed_ops").inc(128)
    assert bp.decide() == "shed"
    # signal recedes below resume with no new evidence -> readmit
    sig.set(0.4)
    m.counter("admission_readmits").inc()
    assert bp.decide() == "admit"
    # decisions were themselves recorded
    snap = m.snapshot()
    assert snap['backpressure_decisions{decision="shed"}'] == 1
    assert snap['backpressure_decisions{decision="admit"}'] == 2


@pytest.mark.tier1
def test_backpressure_from_live_admission_metrics():
    """End to end: a burst through an admission-gated batcher publishes
    trips/deferred/shed into the registry, and a BackpressureController
    reading that registry sheds."""
    m = MetricsRegistry()
    ops = FilterOps(backend="pallas", evict_rounds=16)
    state = jfilter.make_state(64, buffer_buckets=64)
    batcher = FilterOpBatcher(
        ops, state, stash=kops.make_stash(8), wave_slots=WS,
        double_buffer=True, metrics=m,
        admission=AdmissionConfig(high_water=0.3, low_water=0.1))
    bp = BackpressureController(m)
    assert bp.decide() == "admit"
    rng = np.random.RandomState(2)
    for _ in range(12):  # overload a tiny table: 12 x 64 lanes into 256 slots
        batcher.submit("insert",
                       rng.randint(1, 2 ** 62, size=WS,
                                   dtype=np.int64).astype(np.uint64))
    batcher.drain()
    snap = m.snapshot()
    assert snap.get("filter_deferred_waves", 0) >= 1
    assert snap.get("filter_shed_ops", 0) >= 1
    assert snap.get("admission_trips", 0) >= 1
    assert bp.decide() == "shed"


@pytest.mark.tier1
def test_admission_controller_transition_counters():
    class Fills:
        def __init__(self):
            self.v = (0.0, 0.0)

        def fills(self):
            return self.v

    m = MetricsRegistry()
    f = Fills()
    ctl = AdmissionController(filt=f, config=AdmissionConfig(
        high_water=0.5, low_water=0.2), metrics=m)
    assert ctl.peek()
    f.v = (1.0, 1.0)
    assert not ctl.peek()          # trip
    assert not ctl.peek()          # still tripped: no double count
    f.v = (0.0, 0.0)
    assert ctl.peek()              # readmit
    assert m.counter("admission_trips").value() == 1
    assert m.counter("admission_readmits").value() == 1
    assert m.gauge("admission_peak_signal").value() == 1.0


@pytest.mark.tier1
def test_measure_fp_fn_match_scalar_loop(rng):
    ocf = OCF(OcfConfig(capacity=1 << 10, fp_bits=8))
    inserted = rng.randint(1, 2 ** 62, size=600,
                           dtype=np.int64).astype(np.uint64)
    ocf.insert(inserted)
    probes = rng.randint(1, 2 ** 62, size=2000,
                         dtype=np.int64).astype(np.uint64)
    mixed = np.concatenate([probes, inserted[:100]])

    # the scalar ground-truth loop the vectorized path replaced
    absent = np.array([not ocf.contains_key_exact(int(k)) for k in mixed])
    hits = ocf.lookup(mixed)
    assert measure_false_positives(ocf, mixed) == int(np.sum(hits & absent))
    assert measure_false_negatives(ocf, inserted) == 0
    present = ocf.contains_keys_exact(mixed)
    np.testing.assert_array_equal(present, ~absent)


@pytest.mark.tier1
def test_keystore_contains_batch_duplicates_and_empty():
    ks = VectorKeystore()
    assert ks.contains_batch(np.array([1, 2], np.uint64)).tolist() == \
        [False, False]
    ks.add(np.array([5, 5, 9], np.uint64))
    got = ks.contains_batch(np.array([9, 5, 7, 5, 0], np.uint64))
    assert got.tolist() == [True, True, False, True, False]
    ks.remove(np.array([5, 5], np.uint64))
    assert ks.contains_batch(np.array([5], np.uint64)).tolist() == [False]


# ---------------------------------------------------------- registry ----


@pytest.mark.tier1
def test_registry_counter_gauge_histogram():
    m = MetricsRegistry()
    m.counter("c").inc(2, kind="a")
    m.counter("c").inc(kind="b")
    assert m.counter("c").value(kind="a") == 2
    m.gauge("g").set(3.0)
    m.gauge("g").set_max(1.0)
    assert m.gauge("g").value() == 3.0
    h = m.histogram("h", buckets=(1, 2, 4))
    h.observe(0.5)
    h.observe(3)
    h.observe(100)
    h.observe_counts([1, 0, 0, 0])
    s = h.series()[()]
    assert s.counts == [2.0, 0.0, 1.0, 1.0]
    with pytest.raises(ValueError):
        m.histogram("h", buckets=(1, 2, 8))
    with pytest.raises(TypeError):
        m.gauge("c")
    with pytest.raises(ValueError):
        h.observe_counts([1, 2])


@pytest.mark.tier1
def test_registry_exports(tmp_path):
    m = MetricsRegistry(ring_capacity=4)
    m.counter("filter_waves").inc(3, kind="insert")
    m.histogram("lat", buckets=(10, 100)).observe(42)
    for i in range(6):
        m.record_wave({"i": i})
    # ring wrapped: only the last 4 records, in order
    assert [r["i"] for r in m.ring.records()] == [2, 3, 4, 5]

    path = tmp_path / "m.jsonl"
    m.to_jsonl(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert any(ln.get("metric") == "filter_waves" for ln in lines)
    assert sum(1 for ln in lines if ln["type"] == "wave") == 4

    text = m.prometheus_text()
    assert 'filter_waves_total{kind="insert"} 3.0' in text
    assert 'lat_bucket{le="+Inf"} 1.0' in text
    assert "# TYPE lat histogram" in text


@pytest.mark.tier1
def test_trace_recorder_perfetto_shape(tmp_path):
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    tr = TraceRecorder(process_name="test", clock=clock)
    with tr.span("outer", kind="insert"):
        with tr.span("inner"):
            pass
    tr.instant("mark")
    path = tmp_path / "trace.json"
    tr.save(str(path))
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    names = [e["name"] for e in events]
    assert "outer" in names and "inner" in names and "mark" in names
    spans = [e for e in events if e.get("ph") == "X"]
    assert all(e["dur"] > 0 for e in spans)
    inner = next(e for e in events if e["name"] == "inner")
    outer = next(e for e in events if e["name"] == "outer")
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"]["kind"] == "insert"


def _profiled(tmp_path, body):
    """Run ``body`` under the JAX profiler -> {event name: [stats]} of the
    host events recorded."""
    import glob

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    seen.setdefault(e.name, []).append(dict(e.stats))
    return seen


@pytest.mark.tier1
def test_trace_recorder_profiler_mode_keeps_no_events(tmp_path):
    """In profiler mode a span is one annotation, named plainly, with its
    arguments as stats; the recorder itself keeps nothing."""
    tr = TraceRecorder(jax_profiler=True)

    def body():
        with tr.span("wave_dispatch", wave=3, kind="lookup", n=1):
            with tr.span("wave_upload", wave=3, kind="lookup", n=1):
                pass
        tr.instant("recovered", event="shard")

    seen = _profiled(tmp_path, body)
    assert tr.events == []
    with pytest.raises(ValueError):
        tr.save(str(tmp_path / "trace.json"))
    assert {"wave_dispatch", "wave_upload", "recovered"} <= set(seen)
    stats = seen["wave_dispatch"][0]
    assert (stats["wave"], stats["kind"], stats["n"]) == (3, "lookup", 1)


@pytest.mark.tier1
def test_trace_recorder_records_gc_pauses(tmp_path):
    import gc

    TraceRecorder(jax_profiler=True)
    TraceRecorder(jax_profiler=True)
    from repro.obs.trace import _gc_span
    assert gc.callbacks.count(_gc_span) == 1     # hooked once per process
    seen = _profiled(tmp_path, lambda: gc.collect(2))
    assert {"generation": 2} in seen["gc_pause"]


# ------------------------------------------------- merge properties -----
#
# NOT tier-1: hypothesis is an optional dev dependency.


def test_telemetry_merge_associative_commutative():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.kernels.telemetry import (FilterTelemetry, empty_telemetry,
                                         merge)
    import jax.numpy as jnp

    def mk(vals):
        u32 = lambda x: jnp.asarray(x, jnp.uint32)  # noqa: E731
        return FilterTelemetry(
            kick_hist=u32(vals[:8]), probe_depth=u32(vals[8:12]),
            stash_spills=u32(vals[12]), stash_fill_hw=u32(vals[13]),
            rollback_lanes=u32(vals[14]), selector_bumps=u32(vals[15]),
            overflow_lanes=u32(vals[16]), table_deletes=u32(vals[17]),
            stash_deletes=u32(vals[18]))

    vec = st.lists(st.integers(min_value=0, max_value=2 ** 20),
                   min_size=19, max_size=19)

    @settings(max_examples=50, deadline=None)
    @given(vec, vec, vec)
    def check(a, b, c):
        ta, tb, tc = mk(a), mk(b), mk(c)
        left = merge(merge(ta, tb), tc)
        right = merge(ta, merge(tb, tc))
        for x, y in zip(left, right):
            assert jnp.array_equal(x, y)
        ab, ba = merge(ta, tb), merge(tb, ta)
        for x, y in zip(ab, ba):
            assert jnp.array_equal(x, y)
        ea = merge(empty_telemetry(), ta)
        for x, y in zip(ea, ta):
            assert jnp.array_equal(x, y)

    check()
