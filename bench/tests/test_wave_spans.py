"""The readers of the batcher's wave steps: each on a hand-built reduced
trace, then all of them on a traced run of the one-chip cell on the CPU,
whose spans must sit, under their plain names, inside the waves."""
import glob
import os
import shutil

import numpy as np
import pytest

from bench import harness, run, trace_reduce, wave_spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 17
ONE = "kvfilter-1chip.read_latest"
SMALL_1 = {"n_buckets": 1 << 12, "setup_chunk": 1 << 10, "check_sample": 2048}
MIX_1 = {"rate_per_s": 250.0}

NEW = ["batcher.prepare_ms", "batcher.upload_ms", "filterops.enqueue_ms",
       "batcher.occupancy_ms", "batcher.harvest_wait_ms",
       "batcher.harvest_fetch_ms", "batcher.calls_per_wave"]
IN_DISPATCH = ("wave_prepare", "wave_upload", "wave_occupancy")
IN_HARVEST = ("harvest_wait", "harvest_fetch")


def reduced(spans):
    return trace_reduce.Reduced(window_s=1.0, n_devices=1, busy_s=[0.0],
                                programs={}, ops={}, gaps=[], spans=spans)


def read(name, spans):
    return harness.metric_reader(name)({"reduced": reduced(spans)})


def wave(t, *, op="filterops.lookup_with_stash", slow=1):
    """One wave at ``t`` ns: its dispatch with steps 1-4 ms long (times
    ``slow``), its harvest with steps 5 and 6 ms long, and JAX's calls."""
    ms = 1_000_000
    out = [("wave_dispatch", t, t + 70 * ms),
           ("wave_prepare", t, t + 1 * ms * slow),
           ("wave_upload", t + 10 * ms, t + (10 + 2 * slow) * ms),
           ("shard_args", t + 10 * ms, t + 11 * ms),
           ("DevicePutWithSharding", t + 10 * ms, t + 11 * ms),
           ("wave_occupancy", t + 40 * ms, t + (40 + 4 * slow) * ms),
           ("PjitFunction(not_equal)", t + 40 * ms, t + 41 * ms),
           ("PjitFunction(not_equal)", t + 40 * ms + 1, t + 41 * ms - 1),
           ("DevicePut", t + 40 * ms + 2, t + 40 * ms + 9),
           ("wave_harvest", t + 80 * ms, t + 95 * ms),
           ("harvest_wait", t + 80 * ms, t + 85 * ms),
           ("harvest_fetch", t + 85 * ms, t + 91 * ms),
           ("np.asarray(jax.Array)", t + 85 * ms, t + 86 * ms)]
    if op:
        out += [(op, t + 20 * ms, t + (20 + 3 * slow) * ms),
                ("PjitFunction(probe_emulated)", t + 20 * ms, t + 21 * ms)]
    return out


def test_step_medians():
    spans = (wave(0) + wave(100_000_000, op="filterops.insert_spill")
             + wave(200_000_000, slow=3) + [("wait_due", 96_000_000,
                                             99_000_000)])
    assert read("batcher.prepare_ms", spans) == 1.0
    assert read("batcher.upload_ms", spans) == 2.0
    assert read("filterops.enqueue_ms", spans) == 3.0
    assert read("batcher.occupancy_ms", spans) == 4.0
    assert read("batcher.harvest_wait_ms", spans) == 5.0
    assert read("batcher.harvest_fetch_ms", spans) == 6.0


def test_wave_without_a_table_op():
    """A wave with no ``filterops.*`` child counts in every other step."""
    spans = wave(0, op=None) + wave(100_000_000, op=None, slow=2) + \
        wave(200_000_000, slow=5)
    assert read("filterops.enqueue_ms", spans) == 15.0
    assert read("batcher.prepare_ms", spans) == 2.0
    assert read("filterops.enqueue_ms", wave(0, op=None)) is None
    # a step span outside every wave is not a wave's step
    assert read("batcher.prepare_ms", [("wave_prepare", 0, 5)]) is None


def test_calls_per_wave():
    # per wave: the upload's transfer, the occupancy's launch (its inner
    # twin not counted) and scalar transfer, the probe's launch
    assert read("batcher.calls_per_wave", wave(0)) == 4.0
    spans = wave(0) + wave(100_000_000, op=None)
    assert read("batcher.calls_per_wave", spans) == 3.5
    # calls outside the waves are not the batcher's
    spans += [("PjitFunction(add)", 96_000_000, 97_000_000)]
    assert read("batcher.calls_per_wave", spans) == 3.5
    # the phases of one CPU transfer count once
    cpu = [("wave_dispatch", 0, 100),
           ("batched_copy_array_to_devices_with_sharding", 10, 50),
           ("BatchedCopyToDeviceWithSharding create batch", 11, 20),
           ("BatchedCopyToDeviceWithSharding: dispatch", 21, 30)]
    assert read("batcher.calls_per_wave", cpu) == 1.0


@pytest.mark.parametrize("name", NEW)
def test_window_without_waves(name):
    assert read(name, []) is None
    assert read(name, [("wait_due", 0, 10), ("PjitFunction(add)", 2, 3),
                       ("wave_prepare", 4, 5)]) is None


# ------------------------------------------ the cell, traced on the CPU --


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the cell at the small size -> (metric values,
    the host events of the trace as (name, start, end, stats))."""
    r = tmp_path_factory.mktemp("bench_root")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), r)
    shutil.copytree(os.path.join(ROOT, "bench"), r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    # A traced window is short: the CPU profiler records every eager op.
    _cell, ctx, out, values, red = run.measure(
        ONE, seed=SEED, seconds=0.04, trace=True, root=str(r),
        require_tpu=False, cache=False, config_override=SMALL_1,
        mix_override=MIX_1)
    assert harness.is_correct(out.checks)
    import jax
    path, = glob.glob(os.path.join(ctx.trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats})
                           for e in line.events]
    return values, red, events


def test_traced_cell_reports_every_step(traced):
    values, _red, _events = traced
    for name in NEW:
        assert values[name] is not None and np.isfinite(values[name]), name
        assert values[name] > 0, name
    assert values["batcher.dispatch_ms"] > 0


def test_traced_steps_nest_in_their_waves(traced):
    _values, red, events = traced
    inner = wave_spans.in_waves(red.spans)
    names = {n for n, _s, _e in inner}
    for step in IN_DISPATCH + IN_HARVEST + ("filterops.lookup_with_stash",):
        assert step in names, step
        # every one of them lies inside a wave
        assert sum(n == step for n, _s, _e in red.spans) == \
            sum(n == step for n, _s, _e in inner), step
    dispatch = [(s, e) for n, s, e in red.spans if n == "wave_dispatch"]
    harvest = [(s, e) for n, s, e in red.spans if n == "wave_harvest"]
    for n, s, e in inner:
        parents = harvest if n in IN_HARVEST else dispatch \
            if n in IN_DISPATCH or n.startswith("filterops.") else None
        if parents is not None:
            assert any(a <= s and e <= b for a, b in parents), n

    # each step carries its wave's number, kind and size; a wave's
    # dispatch and harvest share the number
    by_wave = {}
    for n, _s, _e, stats in events:
        if n in wave_spans.WAVES + IN_DISPATCH + IN_HARVEST or \
                n.startswith("filterops."):
            assert {"wave", "kind", "n"} <= set(stats), n
            by_wave.setdefault(stats["wave"], set()).add(n)
    full = [w for w, steps in by_wave.items() if "wave_harvest" in steps]
    assert full
    for w in full:
        assert set(IN_DISPATCH + IN_HARVEST) <= by_wave[w]
        assert "wave_dispatch" in by_wave[w]
