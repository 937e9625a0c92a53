"""The trace reduction, the peaks table and the bytes-per-op model."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import costmodel, peaks, trace_reduce

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="other thread", events=[ev("noise", 0, 10_000)]),
        NS(name="main", events=[
            ev("bench_window", 1_000, 10_000),
            ev("wave_dispatch", 1_000, 2_000),
            ev("PjitFunction(probe)", 1_500, 500),
            ev("wait_due", 6_000, 3_000),
            ev("wave_harvest", 9_000, 3_000),  # runs past the window
        ])])
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_probe(12)", 2_000, 3_000),
                                       ev("jit_insert(3)", 9_500, 2_000)]),
        NS(name="XLA Ops", events=[ev("fusion.1", 2_000, 1_000),
                                   ev("all-to-all.2", 2_500, 2_500),
                                   ev("fusion.1", 9_500, 2_000),
                                   ev("early", 0, 1_500)])])
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name="XLA Modules", events=[ev("jit_probe(7)", 2_000, 1_000)]),
        NS(name="XLA Ops", events=[ev("fusion.1", 2_000, 1_000)]),
        NS(name="Async XLA Ops", events=[ev("all-to-all.9", 2_000, 500)])])
    other = NS(name="/device:CUSTOM:Megascale Trace", lines=[
        NS(name="XLA Ops", events=[ev("fusion.1", 1_000, 9_000)])])
    return [dev1, host, other, dev0]


def test_busy_programs_ops_and_gaps():
    r = trace_reduce.reduce_planes(planes())
    assert r.window_s == 10_000 / 1e9 and r.n_devices == 2
    # dev0: [1000,1500) early op clipped, [2000,5000) ops, [9500,11000) clipped
    assert r.busy_s == [(500 + 3_000 + 1_500) / 1e9, 1_000 / 1e9]
    assert r.program_s(r"probe") == (3_000 + 1_000) / 1e9
    assert r.ops["all-to-all.2"] + r.ops["all-to-all.9"] == 3_000 / 1e9
    assert r.top_programs(1) == [["jit_probe", 2_000 / 1e9]]
    gaps = dict((n, s) for s, n in r.gaps)
    assert gaps["PjitFunction(probe)"] == 500 / 1e9      # [1500, 2000)
    assert gaps["wait_due"] == 4_500 / 1e9               # [5000, 9500)
    assert r.top_gaps(1) == [["wait_due", 4_500 / 1e9]]
    assert r.span_durations_s("wave_dispatch") == [2_000 / 1e9]
    assert r.span_durations_s("wave_harvest") == [2_000 / 1e9]   # clipped


def test_trace_without_window_is_refused():
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("wave_dispatch", 0, 5)])])
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([host])


def test_recorded_chip_trace():
    """A short window recorded on one v5e: a few lookup and insert waves."""
    r = trace_reduce.reduce_file(os.path.join(FIXTURES,
                                              "probe_window.xplane.pb"))
    assert r.n_devices == 1
    assert 0 < r.busy_s[0] < r.window_s
    assert r.program_s(r"probe_emulated") > 0
    assert r.span_durations_s("wave_dispatch")
    assert sum(s for s, _n in r.gaps) + r.busy_s[0] == pytest.approx(
        r.window_s, rel=1e-6)


def test_peaks_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and "source" in p
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


def test_costmodel():
    assert costmodel.probe_bytes(1, 0, bucket_size=4, stash_slots=0) == 41
    assert costmodel.probe_bytes(10, 2, bucket_size=4,
                                 stash_slots=1024) == 410 + 2 * 8192
    assert costmodel.roofline_pct(819e9, 1.0, 819e9) == 100.0
    assert costmodel.roofline_pct(0, 1.0, 819e9) is None
