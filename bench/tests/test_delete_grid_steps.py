"""The reader of ``delete.grid_steps``: on a hand-built reduced trace, and on
the trace of a pump running routed deletes on one CPU device."""
import pytest

from bench.tests.test_shard_metrics import MS, call, read, reduced


def test_delete_grid_steps_per_routed_delete():
    spans = (call(0, "delete") + call(100, "delete") + call(200, "insert")
             + [("delete_grid_step", (10 + i) * MS, (10 + i) * MS)
                for i in range(7)])
    assert read("delete.grid_steps", reduced(spans)) == pytest.approx(3.5)
    assert read("delete.grid_steps", reduced(call(0, "delete"))) is None
    assert read("delete.grid_steps", reduced()) is None


def test_delete_grid_steps_of_a_traced_pump():
    """A pump on one CPU device running the kernel arm, traced: the reader
    gives the steps its delete calls carry, those the shard-local delete
    runs over the lanes routing gives the shard (every key first in its one
    row of ``2n + 1`` slots)."""
    import numpy as np

    from repro.core import distributed as dist
    from repro.kernels import ops as kops
    from repro.kernels.delete import live_steps
    from repro.launch.mesh import make_mesh
    from repro.obs import TraceRecorder
    from repro.serving.scheduler import DeferredWritePump

    tr = TraceRecorder()
    state = dist.make_sharded_state(1, 1 << 10, 4, stash_slots=64)
    pump = DeferredWritePump(make_mesh((1,), ("data",)), "data", state,
                             fp_bits=16, backend="pallas", tracer=tr)
    keys = np.arange(1, 1201, dtype=np.uint64) * 0x9E3779B97F4A7C15
    for part in (keys[:700], keys[700:]):
        pump.call("insert", part)
        pump.run_until_drained()
    calls = [pump.call("delete", part) for part in (keys[:300],
                                                    keys[300:])]
    pump.run_until_drained()
    assert all(c.results.all() for c in calls)
    spans = [(e["name"], 1000 * e["ts"], 1000 * (e["ts"] + e.get("dur", 0)))
             for e in tr.events if e.get("ph") in ("X", "i")]
    expect = []
    for n in (300, 900):
        lanes = 2 * n + 1
        block = kops.delete_block(lanes, table_bytes=(1 << 10) * 4 * 4)
        valid = np.pad(np.arange(lanes) < n, (0, -lanes % block))
        expect.append(int(live_steps(valid, block).sum()))
    assert expect[0] < expect[1]
    assert read("delete.grid_steps", reduced(spans)) == pytest.approx(
        sum(expect) / 2)
