"""Elastic resharding: pair routing, live split/merge, cutover protocol.

The routing layer first: ``owner_shard_pair`` must be (a) identical across
the np/jnp twins, (b) derivable from EITHER bucket of a candidate pair (the
involution invariance migration leans on — a resident slot knows only the
bucket it sits in), and (c) hierarchical across pow2 shard counts
(``owner(2n) mod n == owner(n)``), which is what makes a 2x split a strict
one-way scatter.

Then the migration itself, in a forced-4-device subprocess: a live 2->4
split and 4->2 merge over a ``DeferredWritePump`` with a concurrent write
stream parked mid-cutover — zero false negatives on everything previously
acknowledged, per-shard content parity against ``PyStashFilter`` oracles
rebuilt at the new shard count (multisets of (pair-id, fingerprint) — the
placement-schedule-free form of bit-parity), the parked backlog fully
drained, and the recovery metrics + ``pump_resubmit``/``elastic_*`` spans
exported.  Mesh tests run in subprocesses so the forced host-device count
doesn't leak (same pattern as test_distributed_write.py).
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import hashing
from repro.distributed import elastic

pytestmark = pytest.mark.tier1

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
        "JAX_PLATFORMS": "cpu"}


def _run(script):
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600, env=_ENV)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ------------------------------------------------------ pair routing ----


def test_owner_pair_np_jnp_parity():
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    nb, fp_bits = 128, 16
    hi = rng.randint(0, 2**32, 512).astype(np.uint32)
    lo = rng.randint(0, 2**32, 512).astype(np.uint32)
    for n_shards in (2, 4, 8):
        o_np = hashing.owner_shard_key_pair_np(hi, lo, nb, fp_bits, n_shards)
        o_j = np.asarray(hashing.owner_shard_key_pair(
            jnp.asarray(hi), jnp.asarray(lo), nb, fp_bits, n_shards))
        assert np.array_equal(o_np, o_j)
        assert o_np.max() < n_shards


def test_owner_pair_bucket_invariance():
    """The owner must be computable from EITHER bucket of the pair — a
    migrating slot only knows the bucket it happens to sit in."""
    rng = np.random.RandomState(4)
    nb = 64
    b = rng.randint(0, nb, 1024).astype(np.uint32)
    fp = rng.randint(1, 2**16, 1024).astype(np.uint32)
    alt = hashing.alt_index_np(b, fp, nb)
    for n_shards in (2, 4):
        o1 = hashing.owner_shard_pair_np(b, fp, nb, n_shards)
        o2 = hashing.owner_shard_pair_np(alt, fp, nb, n_shards)
        assert np.array_equal(o1, o2)


def test_owner_pair_pow2_hierarchy():
    """owner(2n) mod n == owner(n): a split moves shard s's entries only to
    {s, s+n}, a merge folds s+n onto s — the elastic invariant."""
    rng = np.random.RandomState(5)
    nb, fp_bits = 256, 16
    hi = rng.randint(0, 2**32, 2048).astype(np.uint32)
    lo = rng.randint(0, 2**32, 2048).astype(np.uint32)
    for n in (1, 2, 4, 8):
        o_n = hashing.owner_shard_key_pair_np(hi, lo, nb, fp_bits, n)
        o_2n = hashing.owner_shard_key_pair_np(hi, lo, nb, fp_bits, 2 * n)
        assert np.array_equal(o_2n % n, o_n)
    # and the pair hash actually spreads load across shards
    o4 = hashing.owner_shard_key_pair_np(hi, lo, nb, fp_bits, 4)
    counts = np.bincount(o4, minlength=4)
    assert (counts > 0.5 * len(hi) / 4).all(), counts


def test_largest_mesh_compat():
    """Every mesh comes from the one helper, ``launch.mesh.make_mesh``:
    all axes Auto (jax.make_mesh's default is Explicit, under which the
    routed ops' gathers fail) and devices in the order given."""
    import jax
    from jax.sharding import AxisType
    mesh = elastic.largest_mesh(model_parallel=1)
    assert mesh.shape["model"] == 1
    assert mesh.shape["data"] == len(jax.devices())
    assert mesh.axis_types == (AxisType.Auto,) * 2
    fm = elastic.filter_mesh(1)
    assert fm.axis_types == (AxisType.Auto,)
    assert list(fm.devices.flat) == jax.devices()[:1]


# ----------------------------------------------- live split/merge -------


SPLIT_MERGE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as dist, hashing
    from repro.distributed import elastic
    from repro.obs import MetricsRegistry, TraceRecorder, RecoveryMetrics
    from repro.serving.scheduler import DeferredWritePump
    from repro.streaming.oracle import PyStashFilter

    NB, BS, FP, SS = 32, 4, 16, 32
    CF = 8.0

    def pair_multiset(table, stash, nb):
        # {(pair-id, fp)} with multiplicity: the placement-free content
        # identity (pair-id = min(bucket, alt(bucket, fp))).
        out = []
        t = np.asarray(table)
        for b in range(t.shape[0]):
            for fp in t[b][t[b] != 0]:
                alt = int(hashing.alt_index_np(np.uint32(b), np.uint32(fp),
                                               nb))
                out.append((min(b, alt), int(fp)))
        s = np.asarray(stash)
        for fp, bkt in zip(s[0][s[0] != 0], s[1][s[0] != 0]):
            alt = int(hashing.alt_index_np(np.uint32(bkt), np.uint32(fp),
                                           nb))
            out.append((min(int(bkt), alt), int(fp)))
        return sorted(out)

    def oracle_multisets(keys, n_shards):
        hi, lo = hashing.key_to_u32_pair_np(keys)
        owner = hashing.owner_shard_key_pair_np(hi, lo, NB, FP, n_shards)
        oracles = [PyStashFilter(n_buckets=NB, bucket_size=BS, fp_bits=FP,
                                 stash_slots=SS) for _ in range(n_shards)]
        for k, o in zip(keys, owner):
            assert oracles[o].insert(int(k)), "oracle overfull"
        out = []
        for o in oracles:
            ms = pair_multiset(o.table, np.zeros((2, 1)), NB)
            for fp, bkt in o.stash:
                alt = int(hashing.alt_index_np(np.uint32(bkt),
                                               np.uint32(fp), NB))
                ms.append((min(int(bkt), alt), int(fp)))
            out.append(sorted(ms))
        return out

    rng = np.random.RandomState(11)
    raw = rng.randint(0, 2**63, size=96, dtype=np.int64).astype(np.uint64)
    hi, lo = hashing.key_to_u32_pair_np(raw)

    m2 = elastic.filter_mesh(2)
    m4 = elastic.filter_mesh(4)
    reg, tr = MetricsRegistry(), TraceRecorder()
    rec = RecoveryMetrics(metrics=reg, tracer=tr)
    pump = DeferredWritePump(
        m2, "data", dist.make_sharded_state(2, NB, BS, stash_slots=SS),
        fp_bits=FP, backend="jnp", donate=False, metrics=reg, tracer=tr,
        route="pair", capacity_factor=CF)
    ok, _ = pump.submit(hi, lo)
    pump.run_until_drained()
    assert pump.pending == 0 and pump.stats.failed == 0

    # -- concurrent stream arrives mid-cutover: must park, then drain --
    raw2 = rng.randint(0, 2**63, size=32, dtype=np.int64).astype(np.uint64)
    h2, l2 = hashing.key_to_u32_pair_np(raw2)
    ctrl = elastic.ElasticController(pump, axis="data", recovery=rec)
    pump.hold()
    ok2, def2 = pump.submit(h2, l2)
    parked_during_window = (not ok2.any()) and bool(def2.all())
    pend_mid = pump.pending
    rep_split = ctrl.split(m4)

    all_keys = np.concatenate([raw, raw2])
    ahi, alo = hashing.key_to_u32_pair_np(all_keys)
    hits4, _ = dist.distributed_lookup(
        m4, "data", pump.state, jnp.asarray(ahi), jnp.asarray(alo),
        fp_bits=FP, backend="jnp", route="pair", capacity_factor=CF)
    split_fns = int((~np.asarray(hits4)).sum())

    dev_ms4 = [pair_multiset(pump.state.tables[s], pump.state.stashes[s],
                             NB) for s in range(4)]
    parity4 = dev_ms4 == oracle_multisets(all_keys, 4)

    # -- merge back 4 -> 2 --
    rep_merge = ctrl.merge(m2)
    hits2, _ = dist.distributed_lookup(
        m2, "data", pump.state, jnp.asarray(ahi), jnp.asarray(alo),
        fp_bits=FP, backend="jnp", route="pair", capacity_factor=CF)
    merge_fns = int((~np.asarray(hits2)).sum())
    dev_ms2 = [pair_multiset(pump.state.tables[s], pump.state.stashes[s],
                             NB) for s in range(2)]
    parity2 = dev_ms2 == oracle_multisets(all_keys, 2)

    # -- small-cap streaming: the same split must take multiple rounds --
    seed = dist.make_sharded_state(2, NB, BS, stash_slots=SS)
    seed, sok, sdef, _ = dist.distributed_insert(
        m2, "data", seed, jnp.asarray(hi), jnp.asarray(lo), fp_bits=FP,
        backend="jnp", route="pair", capacity_factor=CF)
    small, rep_small = elastic.split_state(m4, "data", seed, cap=4)
    hits_s, _ = dist.distributed_lookup(
        m4, "data", small, jnp.asarray(hi), jnp.asarray(lo), fp_bits=FP,
        backend="jnp", route="pair", capacity_factor=CF)
    small_fns = int((~np.asarray(hits_s)[np.asarray(sok)]).sum())

    snap = reg.snapshot()
    span_names = [e["name"] for e in tr.events]
    print(json.dumps({
        "parked_during_window": bool(parked_during_window),
        "pend_mid": int(pend_mid),
        "pend_after": int(pump.pending),
        "split_fns": split_fns, "merge_fns": merge_fns,
        "split_moved": rep_split.keys_moved,
        "merge_moved": rep_merge.keys_moved,
        "split_failed": rep_split.failed, "merge_failed": rep_merge.failed,
        "parity4": bool(parity4), "parity2": bool(parity2),
        "small_rounds": rep_small.rounds, "small_fns": small_fns,
        "metrics": {k: v for k, v in snap.items()
                    if k.startswith(("elastic_",))},
        "has_resubmit_span": "pump_resubmit" in span_names,
        "has_split_span": "elastic_split" in span_names,
        "has_merge_span": "elastic_merge" in span_names,
    }))
""")


def test_live_split_merge_subprocess():
    """2->4 split and 4->2 merge, live, with a parked concurrent stream:
    zero false negatives, oracle content parity, backlog drained."""
    res = _run(SPLIT_MERGE_SCRIPT)
    assert res["parked_during_window"], "held pump must park fresh submits"
    assert res["pend_mid"] == 32
    assert res["pend_after"] == 0, "backlog must drain after cutover"
    assert res["split_fns"] == 0, "split lost keys (false negatives)"
    assert res["merge_fns"] == 0, "merge lost keys (false negatives)"
    assert res["split_moved"] > 0 and res["merge_moved"] > 0
    assert res["split_failed"] == 0 and res["merge_failed"] == 0
    assert res["parity4"], "post-split content != 4-shard oracle rebuild"
    assert res["parity2"], "post-merge content != 2-shard oracle rebuild"
    assert res["small_rounds"] > 1, "tiny cap must stream multiple rounds"
    assert res["small_fns"] == 0
    m = res["metrics"]
    assert m['elastic_keys_migrated{direction="split"}'] > 0
    assert m['elastic_keys_migrated{direction="merge"}'] > 0
    assert m["elastic_deferred_backlog"] == 0
    assert m['elastic_time_to_recover_s{event="elastic_split"}'] > 0
    assert m['elastic_time_to_recover_s{event="elastic_merge"}'] > 0
    assert m["elastic_backlog_drained_lanes"] >= 32
    assert res["has_resubmit_span"], "pump resubmits must emit spans"
    assert res["has_split_span"] and res["has_merge_span"]
