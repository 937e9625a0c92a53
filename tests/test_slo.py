"""SLO harness tests — submit-path parity, determinism, admission coupling.

The contracts pinned here:

  * the async double-buffered submit path is **bit-for-bit** the sync
    path (same per-wave results, same final device state) — scheduling
    may overlap work, never change answers;
  * single-lane waves through ``FilterOpBatcher`` reproduce the
    sequential oracles (``PyStashFilter`` / ``PyAdaptiveFilter``) op for
    op AND state for state — the batcher adds pipelining, not semantics;
  * a wave is one host->device copy and one program: its answers, the
    planes, stash and fills are the ``FilterOps`` entries' own on
    separate (hi, lo, valid) arrays; a write donates the table when
    ``donate`` is set, and a lookup hands back no state;
  * scenario streams are byte-reproducible from one seed (the bench
    gate's comparability requirement, satellite of ISSUE 8);
  * admission coupling under a burst train: the hysteresis gate defers
    inserts at high water, re-admits below low water, and sheds what a
    sustained overload never lets back in;
  * the latency recorder's op-weighted percentiles are the numbers the
    bench rows claim they are.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import filter as jfilter
from repro.core import hashing
from repro.core.filter_ops import FilterOps
from repro.adaptive.state import make_adaptive_state
from repro.kernels import ops as kops
from repro.serving import scheduler
from repro.serving.scheduler import FilterOpBatcher
from repro.serving.slo import (LatencyRecorder, SloHarness, make_batcher,
                               run_scenario)
from repro.serving.workloads import SCENARIOS, scenario_stream
from repro.streaming.admission import AdmissionConfig
from repro.streaming.oracle import PyAdaptiveFilter, PyStashFilter

pytestmark = [pytest.mark.tier1, pytest.mark.slo]

WS = 64  # small waves keep tier-1 fast; shapes are what compile, not sizes

SMALL = {
    "uniform": dict(waves=8),
    "zipfian": dict(waves=8),
    "adversarial": dict(rounds=2),
    "burst_train": dict(bursts=2, burst_waves=2, gap_waves=2),
    "ttl_churn": dict(waves=8),
    "delete_heavy": dict(waves=9),
}


def _replay(name, *, double_buffer, seed=7):
    """Run a small scenario through a fresh stack -> (batcher, results)."""
    batcher = make_batcher(name, double_buffer=double_buffer, wave_slots=WS)
    waves = []
    for batch in scenario_stream(name, seed, wave_slots=WS, **SMALL[name]):
        wave = batcher.submit(batch.kind, batch.keys)
        waves.append(wave)
        if batch.feedback:
            batcher.flush()
            hits = batch.keys[wave.results]
            if hits.size:
                waves.append(batcher.submit("report", hits))
    batcher.drain()
    return batcher, [w.results for w in waves]


# ------------------------------------------------- async/sync parity ----


@pytest.mark.parametrize("scenario", ["uniform", "burst_train",
                                      "delete_heavy", "adversarial"])
def test_double_buffered_path_is_bit_for_bit(scenario):
    """Double-buffering overlaps host prep with device execution but must
    issue the identical device-call sequence: every wave's results and the
    final filter state match the synchronous path exactly."""
    ba, ra = _replay(scenario, double_buffer=True)
    bs, rs = _replay(scenario, double_buffer=False)
    assert len(ra) == len(rs)
    for x, y in zip(ra, rs):
        assert np.array_equal(x, y)
    assert np.array_equal(np.asarray(ba.state.table),
                          np.asarray(bs.state.table))
    assert int(ba.state.count) == int(bs.state.count)
    if ba.stash is not None:
        assert np.array_equal(np.asarray(ba.stash), np.asarray(bs.stash))
    if hasattr(ba.state, "sels"):
        assert np.array_equal(np.asarray(ba.state.sels),
                              np.asarray(bs.state.sels))


# ------------------------------------------------- oracle parity --------


def _ops_stream(rng, n_ops, pool):
    """A deterministic single-key op mix over a small key pool."""
    ops = []
    inserted = []
    for _ in range(n_ops):
        r = rng.random()
        key = int(pool[rng.integers(pool.size)])
        if r < 0.5 or not inserted:
            ops.append(("insert", key))
            inserted.append(key)
        elif r < 0.7:
            ops.append(("delete", inserted.pop(
                int(rng.integers(len(inserted))))))
        else:
            ops.append(("lookup", key))
    return ops


def test_single_lane_parity_vs_stash_oracle():
    """Single-lane waves == the sequential kernel-faithful oracle, op for
    op and state for state, through spills and deletes."""
    NB, BS, FPB, ER, SS = 16, 4, 12, 8, 8
    rng = np.random.default_rng(11)
    pool = rng.integers(1, 2**63, 160, dtype=np.uint64)
    oracle = PyStashFilter(n_buckets=NB, bucket_size=BS, fp_bits=FPB,
                           evict_rounds=ER, stash_slots=SS)
    batcher = FilterOpBatcher(
        FilterOps(fp_bits=FPB, backend="pallas", evict_rounds=ER),
        jfilter.make_state(NB, BS), stash=kops.make_stash(SS),
        wave_slots=1, double_buffer=True)
    for kind, key in _ops_stream(rng, 120, pool):
        wave = batcher.submit(kind, np.asarray([key], np.uint64))
        expect = getattr(oracle, kind)(key)
        batcher.flush()
        assert bool(wave.results[0]) == expect, (kind, key)
    assert np.array_equal(np.asarray(batcher.state.table), oracle.table)
    assert np.array_equal(np.asarray(batcher.stash), oracle.stash_array())
    assert int(batcher.state.count) == oracle.count


def test_single_lane_parity_vs_adaptive_oracle():
    """Same contract over the adaptive planes, with the report verb in the
    mix: adapted flags, selector plane, and mirror planes all match."""
    NB, BS, FPB, ER, SS = 32, 4, 8, 8, 8
    rng = np.random.default_rng(13)
    members = rng.integers(1, 2**63, 96, dtype=np.uint64)
    probes = rng.integers(1, 2**63, 64, dtype=np.uint64)
    oracle = PyAdaptiveFilter(n_buckets=NB, bucket_size=BS, fp_bits=FPB,
                              evict_rounds=ER, stash_slots=SS)
    batcher = FilterOpBatcher(
        FilterOps(fp_bits=FPB, backend="pallas", evict_rounds=ER),
        make_adaptive_state(NB, BS), stash=kops.make_stash(SS),
        wave_slots=1, double_buffer=True)

    def step(kind, key):
        wave = batcher.submit(kind, np.asarray([key], np.uint64))
        if kind == "report":
            expect = oracle.report_false_positive(int(key))[0]
        else:
            expect = getattr(oracle, kind)(int(key))
        batcher.flush()
        assert bool(wave.results[0]) == expect, (kind, key)

    for key in members:
        step("insert", key)
    for key in probes:        # report every probe that false-positives
        wave = batcher.submit("lookup", np.asarray([key], np.uint64))
        batcher.flush()
        assert bool(wave.results[0]) == oracle.lookup(int(key))
        if wave.results[0]:
            step("report", key)
    for key in members[::3]:
        step("delete", key)
    assert np.array_equal(np.asarray(batcher.state.table), oracle.table)
    assert np.array_equal(np.asarray(batcher.state.sels),
                          oracle.sel_plane_array())
    khi, klo = oracle.key_planes()
    assert np.array_equal(np.asarray(batcher.state.khi), khi)
    assert np.array_equal(np.asarray(batcher.state.klo), klo)


# ------------------------------------------------- determinism ----------


def test_scenario_streams_are_deterministic():
    """One seed => one byte-identical stream, for every scenario (the
    bench-row comparability contract); a different seed must differ."""
    for name in SCENARIOS:
        a = scenario_stream(name, 123, wave_slots=WS, **SMALL[name])
        b = scenario_stream(name, 123, wave_slots=WS, **SMALL[name])
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.kind == y.kind
            assert (x.burst, x.advance, x.feedback) == \
                   (y.burst, y.advance, y.feedback)
            assert np.array_equal(x.keys, y.keys)
        c = scenario_stream(name, 124, wave_slots=WS, **SMALL[name])
        assert any(not np.array_equal(x.keys, y.keys)
                   for x, y in zip(a, c))


def test_serving_bench_streams_are_seed_reproducible():
    """The bench CLI's --seed flag threads one np.random.Generator into
    every generator: two builds at one seed are identical key streams."""
    import sys
    sys.path.insert(0, "benchmarks")
    try:
        import serving_bench
    finally:
        sys.path.pop(0)
    a = serving_bench.make_streams(seed=42, wave_slots=WS)
    b = serving_bench.make_streams(seed=42, wave_slots=WS)
    assert sorted(a) == sorted(b)
    for name in a:
        for x, y in zip(a[name], b[name]):
            assert x.kind == y.kind and np.array_equal(x.keys, y.keys)


# ------------------------------------------------- admission coupling ---


def test_admission_defers_readmits_and_sheds_under_burst():
    """Hysteresis both ways: the burst pushes the fills snapshot past high
    water (insert waves park), deletes pull it below low water (parked
    waves re-launch), and a sustained overload leaves shed ops behind."""
    # double_buffer pinned: the band is tuned against the async path's
    # lagged fills() snapshot, so the hysteresis trajectory must not
    # depend on the host's "auto" resolution
    batcher = make_batcher(
        "burst_train", wave_slots=WS, n_buckets=128, stash_slots=8,
        double_buffer=True,
        admission=AdmissionConfig(high_water=0.18, low_water=0.12))
    stream = scenario_stream("burst_train", 0, wave_slots=WS,
                             bursts=3, burst_waves=4, gap_waves=3)
    report = SloHarness().run(batcher, stream, scenario="burst_admission")
    assert report.deferred_waves > 0          # gate tripped at high water
    readmitted = [s for s in report.recorder.samples if s.deferred]
    assert readmitted                          # ...and re-admitted later
    assert batcher.admission.peak_signal >= 0.18
    # deferred waves carry their queueing delay: their tail cannot beat
    # the admitted-only tail
    admitted = report.recorder.percentiles(exclude_deferred=True)
    assert report.percentiles_us["p99"] >= admitted["p99"]
    lo, hi = batcher.fills()
    assert 0.0 <= lo <= 1.0 and 0.0 <= hi <= 1.0


def test_lookups_and_deletes_bypass_admission():
    """Only inserts are gated: probes add no occupancy and deletes relieve
    it, so a tripped gate must not defer either."""
    state = jfilter.make_state(16, 4)
    batcher = FilterOpBatcher(
        FilterOps(fp_bits=12, backend="pallas", evict_rounds=8),
        state, stash=kops.make_stash(8), wave_slots=WS,
        double_buffer=True,
        admission=AdmissionConfig(high_water=0.0, low_water=-1.0))
    keys = np.arange(1, WS + 1, dtype=np.uint64)
    w_ins = batcher.submit("insert", keys)
    w_look = batcher.submit("lookup", keys)
    w_del = batcher.submit("delete", keys)
    batcher.drain()
    assert w_ins.results is None               # parked forever (shed)
    assert w_look.results is not None and not w_look.results.any()
    assert w_del.results is not None
    assert batcher.stats.shed_ops == WS


def test_double_buffer_auto_resolves_per_host(monkeypatch):
    """``double_buffer="auto"`` picks the async path only where overlap can
    pay: real accelerators always, CPU hosts only with more than one core
    (on a single core the pipelined wave just queues behind the previous
    one).  Explicit flags are never overridden."""
    from repro.serving import scheduler as sched

    def mk(**kw):
        return FilterOpBatcher(FilterOps(fp_bits=12, backend="pallas"),
                               jfilter.make_state(16, 4), wave_slots=4,
                               **kw)

    monkeypatch.setattr(sched.jax, "default_backend", lambda: "cpu")
    monkeypatch.setattr(sched.os, "cpu_count", lambda: 8)
    assert mk().double_buffer
    monkeypatch.setattr(sched.os, "cpu_count", lambda: 1)
    assert not mk().double_buffer
    assert mk(double_buffer=True).double_buffer
    monkeypatch.setattr(sched.jax, "default_backend", lambda: "tpu")
    assert mk().double_buffer
    assert not mk(double_buffer=False).double_buffer


# ------------------------------------------------- fill snapshot --------


def _small_stack(family, stash=True):
    """A 16 x 4 filter, static or adaptive, with an 8-slot stash or none."""
    ops = FilterOps(fp_bits=12, backend="pallas", evict_rounds=8)
    state = (make_adaptive_state(16, 4) if family == "adaptive"
             else jfilter.make_state(16, 4))
    return ops, state, kops.make_stash(8) if stash else None


def _device_fills(state, stash):
    """(table fill, stash fill) read straight from the device arrays."""
    capacity = int(state.n_buckets) * state.table.shape[1]
    return (int(state.count) / capacity,
            0.0 if stash is None
            else int(kops.stash_occupancy(stash)) / stash.shape[1])


def _entry_wave(ops, state, stash, kind, keys):
    """One wave as the ``FilterOps`` entries run it on separate (hi, lo,
    valid) arrays padded to ``WS`` -> (state, stash, answers)."""
    padded = np.zeros(WS, np.uint64)
    padded[:keys.size] = keys
    hi, lo = (jnp.asarray(a) for a in hashing.key_to_u32_pair_np(padded))
    valid = jnp.asarray(np.arange(WS) < keys.size)
    if kind == "lookup":
        if hasattr(state, "sels"):
            res = ops.lookup_adaptive(state, hi, lo, stash=stash)
        elif stash is None:
            res = ops.lookup(state, hi, lo)
        else:
            res = ops.lookup_with_stash(state, stash, hi, lo)
    elif kind == "report":
        state, res, _resident = ops.report_false_positive(state, hi, lo,
                                                          valid=valid)
    elif hasattr(state, "sels"):
        write = ops.insert_adaptive if kind == "insert" \
            else ops.delete_adaptive
        out = write(state, hi, lo, valid=valid, stash=stash)
        state, res = out[0], out[-1]
        stash = out[1] if stash is not None else None
    elif stash is None:
        write = ops.insert if kind == "insert" else ops.delete
        state, res = write(state, hi, lo, valid=valid)
    elif kind == "insert":
        state, stash, res = ops.insert_spill(state, stash, hi, lo,
                                             valid=valid)
    else:
        table, new_stash, res = ops.delete_table(
            state.table, hi, lo, n_buckets=state.n_buckets, valid=valid,
            stash=stash)
        count = (state.count - jnp.sum(res, dtype=jnp.int32)
                 + kops.stash_occupancy(stash)
                 - kops.stash_occupancy(new_stash))
        state, stash = state._replace(table=table, count=count), new_stash
    return state, stash, np.asarray(res)[:keys.size]


@pytest.mark.parametrize("family", ["static", "adaptive"])
@pytest.mark.parametrize("double_buffer", [True, False])
@pytest.mark.parametrize("with_stash", [True, False])
def test_fills_after_every_harvest_match_the_device(family, double_buffer,
                                                    with_stash, monkeypatch):
    """``fills()`` is the device's (count, stash occupancy) as of the last
    harvested wave, whether or not that wave read a snapshot: the batcher
    is built over a pre-filled stash (or none), its stream opens with
    lookups, and mixes inserts, table deletes and (adaptive) reports.
    Every wave is one host->device copy (``stats.uploads``, and one
    ``jax.device_put``), and its answers, the planes, stash and fills are
    those of the same ``FilterOps`` entries called on separate (hi, lo,
    valid) arrays."""
    ops, state, stash = _small_stack(family, with_stash)
    rng = np.random.RandomState(5)
    fill = FilterOpBatcher(ops, state, stash=stash, wave_slots=WS,
                           double_buffer=False)
    live = rng.randint(1, 2 ** 62, size=WS, dtype=np.int64).astype(np.uint64)
    fill.submit("insert", live)
    assert stash is None or int(kops.stash_occupancy(fill.stash)) > 0, \
        "the stream must start over a pre-filled stash"

    puts = []
    monkeypatch.setattr(scheduler.jax, "device_put",
                        lambda x, *a, _put=jax.device_put, **k:
                        puts.append(x) or _put(x, *a, **k))
    b = FilterOpBatcher(ops, fill.state, stash=fill.stash, wave_slots=WS,
                        double_buffer=double_buffer)
    assert b.fills() == _device_fills(b.state, b.stash)
    kinds = ["lookup", "lookup", "delete", "lookup", "insert", "lookup",
             "report", "delete", "lookup", "insert", "report", "lookup"]
    if family == "static":
        kinds = [k for k in kinds if k != "report"]
    waves, after, offered = [], [], []
    for kind in kinds:
        fresh = rng.randint(1, 2 ** 62, size=WS // 2,
                            dtype=np.int64).astype(np.uint64)
        keys = (live[rng.permutation(WS)[:WS // 2]] if kind == "delete"
                else np.concatenate([live[:WS // 4], fresh[:WS // 4]])
                if kind != "insert" else fresh)
        offered.append(keys)
        waves.append(b.submit(kind, keys))
        after.append(_device_fills(b.state, b.stash))
        done = [i for i, w in enumerate(waves) if w.results is not None]
        if done:
            assert b.fills() == after[done[-1]], (kind, len(waves))
    b.flush()
    assert b.fills() == after[-1] == _device_fills(b.state, b.stash)
    assert b.stats.uploads == len(kinds) == len(puts)
    assert [p.shape for p in puts] == [
        ((2 if kind == "lookup" else 3) * WS,) for kind in kinds]

    ref_state, ref_stash = fill.state, fill.stash
    for i, (kind, keys) in enumerate(zip(kinds, offered)):
        ref_state, ref_stash, res = _entry_wave(ops, ref_state, ref_stash,
                                                kind, keys)
        np.testing.assert_array_equal(waves[i].results, res)
        assert after[i] == _device_fills(ref_state, ref_stash), (kind, i)
    for got, want in zip(b.state, ref_state):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (b.stash is None) == (ref_stash is None)
    if with_stash:
        np.testing.assert_array_equal(np.asarray(b.stash),
                                      np.asarray(ref_stash))


@pytest.mark.parametrize("double_buffer", [True, False])
def test_write_waves_donate_and_lookup_waves_keep_the_count(double_buffer):
    """With ``FilterOps(donate=True)`` an insert wave and a table delete
    consume the pre-wave table (updated in place, not copied), while a
    lookup wave hands back no state: ``state.count`` stays the same
    object, so the wave reads no fill snapshot."""
    ops = FilterOps(fp_bits=12, backend="pallas", evict_rounds=8,
                    donate=True)
    b = FilterOpBatcher(ops, jfilter.make_state(16, 4),
                        stash=kops.make_stash(8), wave_slots=WS,
                        double_buffer=double_buffer)
    keys = np.arange(1, WS // 2 + 1, dtype=np.uint64)
    for kind in ("insert", "delete"):
        table = b.state.table
        b.submit(kind, keys)
        b.flush()
        assert table.is_deleted(), kind
    count, table, reads = b.state.count, b.state.table, b.stats.fill_reads
    w = b.submit("lookup", keys)
    b.flush()
    assert b.state.count is count and b.state.table is table
    assert b.stats.fill_reads == reads
    assert not table.is_deleted() and w.results.shape == keys.shape


@pytest.mark.parametrize("with_stash", [True, False])
def test_lookup_wave_program_keeps_the_probe_name(with_stash):
    """The lookup wave's program is named so that ``probe_roofline``'s
    pattern finds its device time: the probe inlines into it, and a trace
    shows only the outer program's name."""
    import re

    from bench.metrics.probe_roofline import PROGRAM
    ops, state, stash = _small_stack("static", with_stash)
    entry = scheduler._wave_entry("lookup", False, stash is not None)
    program = scheduler._wave_program(ops, entry, getattr(FilterOps, entry),
                                      False)
    text = program.lower(
        state._replace(count=None, n_buckets=None),
        (state.count, state.n_buckets), stash,
        jnp.zeros(2 * WS, jnp.uint32)).as_text()
    module = re.search(r"module @(\S+)", text).group(1)
    assert re.search(PROGRAM, module), module


# ------------------------------------------------- recorder & reports ---


def test_recorder_percentiles_are_op_weighted():
    rec = LatencyRecorder()
    rec.observe("lookup", 100.0, ops=990)
    rec.observe("lookup", 1000.0, ops=10, deferred=True)
    p = rec.percentiles()
    assert p["p50"] == 100.0
    assert p["p999"] == 1000.0                # the slow wave IS the tail
    assert rec.percentiles(exclude_deferred=True)["p999"] == 100.0
    assert rec.ops() == 1000
    assert rec.percentiles(kinds=("insert",)) == {
        "p50": 0.0, "p99": 0.0, "p999": 0.0}


def test_report_rows_shape_and_monotonicity():
    """rows() carries the gate-facing names and p50 <= p99 <= p999."""
    rep = run_scenario("uniform", seed=3, wave_slots=WS, warmup=True,
                       stream_kwargs=SMALL["uniform"])
    rows = rep.rows()
    for suffix in ("p50_us", "p99_us", "p999_us", "keys_per_s"):
        assert f"slo_uniform_{suffix}" in rows
    assert rows["slo_uniform_p50_us"] <= rows["slo_uniform_p99_us"] \
        <= rows["slo_uniform_p999_us"]
    assert rows["slo_uniform_keys_per_s"] > 0
    assert rep.ops == sum(s.ops for s in rep.recorder.samples)


def test_ttl_churn_expires_generations():
    rep = run_scenario("ttl_churn", seed=5, wave_slots=WS, warmup=False,
                       stream_kwargs=SMALL["ttl_churn"])
    assert rep.extras["expirations"] > 0       # the ring actually aged
    assert rep.ops == WS * SMALL["ttl_churn"]["waves"]
    assert rep.percentiles_us["p99"] > 0
