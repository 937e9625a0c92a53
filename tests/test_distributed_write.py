"""Distributed write path: routed insert/delete on a 2-shard test mesh.

Three contracts, mirroring the single-node parity ladder:

* **Bit-for-bit** (single-lane): batches carrying exactly one key per owner
  shard make every shard's kernel call a single-lane residue, so the PR-4
  contract — Pallas insert/delete/stash == ``PyStashFilter`` oracle, table
  AND stash, entry for entry — must extend through the all_to_all routing
  unchanged.  This is the strongest possible statement that routing is
  semantics-free.

* **Membership + conservation** (contended): multi-lane batches are
  order-racy by design, so the batched test asserts the weaker invariants
  that survive any schedule — every acknowledged key answers lookups, every
  acknowledgment corresponds to exactly one live entry (table or stash),
  and verified deletes drain the state to empty.

* **Deferred routing overflow**: keys exceeding the all_to_all capacity are
  never attempted, never lost — returned as a deferred batch whose
  resubmission drains to full membership, while the per-shard overflow
  counters feed the EOF signal.

Mesh tests run in subprocesses so the forced host-device count doesn't leak
into other tests (same pattern as test_distributed_ocf.py).
"""
import json
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed as dist
from repro.core import filter as jf
from repro.core import hashing

from conftest import random_keys

pytestmark = pytest.mark.tier1

# JAX_PLATFORMS pinned: without it, backend discovery in the bare-env
# subprocess can stall for minutes on hosts whose accelerator plugins
# time out rather than fail fast.
_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
        "JAX_PLATFORMS": "cpu"}


def _run(script):
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600, env=_ENV)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


ORACLE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as dist, hashing
    from repro.streaming.oracle import PyStashFilter

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("data",))
    NB, BS, FP, ER, SS = 16, 4, 16, 8, 8
    state = dist.make_sharded_state(2, NB, BS, stash_slots=SS)
    oracle = [PyStashFilter(n_buckets=NB, bucket_size=BS, fp_bits=FP,
                            evict_rounds=ER, stash_slots=SS)
              for _ in range(2)]

    # One key per owner shard per step -> every shard-local kernel call is a
    # single valid lane: the bit-for-bit contract applies end to end.
    rng = np.random.RandomState(7)
    raw = rng.randint(0, 2**63, size=4096, dtype=np.int64).astype(np.uint64)
    rhi, rlo = hashing.key_to_u32_pair_np(raw)
    owner = np.asarray(hashing.owner_shard_np(rhi, rlo, 2))
    by_owner = [raw[owner == s] for s in range(2)]
    steps = 72          # 72 keys/shard into 64 slots: evictions + spills
    pairs = [(int(by_owner[0][t]), int(by_owner[1][t])) for t in range(steps)]

    ok_dev, ok_orc = [], []
    for k0, k1 in pairs:
        ks = np.array([k0, k1], dtype=np.uint64)
        hi, lo = hashing.key_to_u32_pair_np(ks)
        state, ok, deferred, _ = dist.distributed_insert(
            mesh, "data", state, jnp.asarray(hi), jnp.asarray(lo),
            fp_bits=FP, backend="pallas", evict_rounds=ER)
        assert not bool(np.asarray(deferred).any())
        ok_dev.append(np.asarray(ok).tolist())
        ok_orc.append([oracle[0].insert(k0), oracle[1].insert(k1)])

    tables_eq = all(
        np.array_equal(np.asarray(state.tables[s]), oracle[s].table)
        for s in range(2))
    stash_eq = all(
        np.array_equal(np.asarray(state.stashes[s]), oracle[s].stash_array())
        for s in range(2))
    spilled = sum(o.spills for o in oracle)

    # Delete the acknowledged half of the stream (even steps), still one
    # lane per shard: stash-parked keys must clear exactly like residents.
    dok_dev, dok_orc = [], []
    for t in range(0, steps, 2):
        k0, k1 = pairs[t]
        if not (ok_orc[t][0] and ok_orc[t][1]):
            continue
        ks = np.array([k0, k1], dtype=np.uint64)
        hi, lo = hashing.key_to_u32_pair_np(ks)
        state, dok, _, _ = dist.distributed_delete(
            mesh, "data", state, jnp.asarray(hi), jnp.asarray(lo),
            fp_bits=FP, backend="pallas")
        dok_dev.append(np.asarray(dok).tolist())
        dok_orc.append([oracle[0].delete(k0), oracle[1].delete(k1)])

    tables_eq2 = all(
        np.array_equal(np.asarray(state.tables[s]), oracle[s].table)
        for s in range(2))
    stash_eq2 = all(
        np.array_equal(np.asarray(state.stashes[s]), oracle[s].stash_array())
        for s in range(2))

    print(json.dumps({
        "ok_match": ok_dev == ok_orc,
        "tables_eq": bool(tables_eq), "stash_eq": bool(stash_eq),
        "spilled": int(spilled),
        "dok_match": dok_dev == dok_orc,
        "tables_eq_after_delete": bool(tables_eq2),
        "stash_eq_after_delete": bool(stash_eq2),
        "n_deletes": len(dok_dev),
    }))
""")


def test_distributed_write_oracle_subprocess():
    """Routed insert/delete == per-shard PyStashFilter, bit for bit."""
    res = _run(ORACLE_SCRIPT)
    assert res["ok_match"], "per-step ack parity"
    assert res["tables_eq"], "shard tables bit-for-bit after inserts"
    assert res["stash_eq"], "shard stashes bit-for-bit after inserts"
    assert res["spilled"] > 0, "workload must actually exercise the stash"
    assert res["n_deletes"] > 0
    assert res["dok_match"], "per-step delete-ack parity"
    assert res["tables_eq_after_delete"]
    assert res["stash_eq_after_delete"], "stash deletes clear in place"


CONTENDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as dist, hashing

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("data",))
    NB, BS, FP = 256, 4, 16            # 2048 slots total
    N = 1800                           # -> 0.879 load when fully placed
    rng = np.random.RandomState(11)
    keys = np.unique(
        rng.randint(1, 2**63, size=2 * N, dtype=np.int64))[:N].astype(
        np.uint64)
    hi, lo = hashing.key_to_u32_pair_np(keys)
    hi, lo = jnp.asarray(hi), jnp.asarray(lo)

    state = dist.make_sharded_state(2, NB, BS, stash_slots=128)
    # max_disp=8: short chains exhaust at this load, forcing stash spills
    # (the jnp arm's chain knob; the kernel arm's is evict_rounds)
    state, ok, deferred, ovf = dist.distributed_insert(
        mesh, "data", state, hi, lo, fp_bits=FP, backend="jnp",
        evict_rounds=64, max_disp=8)
    ok = np.asarray(ok)
    load = float(dist.sharded_occupancy(state))
    hits, _ = dist.distributed_lookup(mesh, "data", state, hi, lo,
                                      fp_bits=FP, backend="jnp")
    live = (int(np.asarray(state.tables != 0).sum())
            + int(np.asarray(state.stashes[:, 0, :] != 0).sum()))
    in_stash = int(np.asarray(state.stashes[:, 0, :] != 0).sum())

    # verified delete of every acknowledged key drains the state to empty
    state2, dok, ddef, _ = dist.distributed_delete(
        mesh, "data", state, hi, lo, fp_bits=FP, backend="jnp")
    residue = (int(np.asarray(state2.tables != 0).sum())
               + int(np.asarray(state2.stashes[:, 0, :] != 0).sum()))

    print(json.dumps({
        "n": int(keys.size),
        "ok": int(ok.sum()),
        "deferred": int(np.asarray(deferred).sum()),
        "load": load,
        "acked_found": int((np.asarray(hits) & ok).sum()),
        "live": live, "in_stash": in_stash,
        "dok": int(np.asarray(dok).sum()),
        "ddeferred": int(np.asarray(ddef).sum()),
        "residue": residue,
    }))
""")


def test_distributed_contended_subprocess():
    """Contended batch at >=0.85 load resolves on-device: membership +
    conservation, then verified deletes drain to empty."""
    res = _run(CONTENDED_SCRIPT)
    assert res["deferred"] == 0, "default capacity absorbs the batch"
    assert res["ok"] == res["n"], "chains + stash place the whole batch"
    assert res["load"] >= 0.85, "the acceptance load is actually reached"
    assert res["acked_found"] == res["ok"], "no false negatives"
    assert res["live"] == res["ok"], "one live entry per acknowledged key"
    assert res["in_stash"] > 0, "contention actually spilled"
    assert res["ddeferred"] == 0
    assert res["dok"] == res["ok"], "every acknowledged key deletes"
    assert res["residue"] == 0, "conservation: deletes drain the state"


OVERFLOW_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as dist, hashing

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("data",))
    NB, BS, FP = 128, 4, 16
    rng = np.random.RandomState(3)
    keys = rng.randint(1, 2**63, size=256, dtype=np.int64).astype(np.uint64)
    hi0, lo0 = hashing.key_to_u32_pair_np(keys)

    state = dist.make_sharded_state(2, NB, BS, stash_slots=32)
    hi, lo = jnp.asarray(hi0), jnp.asarray(lo0)
    state, ok, dfr, ovf = dist.distributed_insert(
        mesh, "data", state, hi, lo, fp_bits=FP, backend="jnp",
        capacity_factor=0.25)
    first_deferred = int(np.asarray(dfr).sum())
    first_ovf = int(np.asarray(ovf).sum())
    both = bool(np.any(np.asarray(ok) & np.asarray(dfr)))

    rounds = 0
    d = np.asarray(dfr)
    while d.any() and rounds < 200:
        idx = np.where(d)[0]
        if len(idx) % 2:
            idx = np.concatenate([idx, idx[:1]])
        hi, lo = hi[idx], lo[idx]
        state, ok, d, _ = dist.distributed_insert(
            mesh, "data", state, hi, lo, fp_bits=FP, backend="jnp",
            capacity_factor=0.25)
        d = np.asarray(d)
        rounds += 1

    hits, _ = dist.distributed_lookup(
        mesh, "data", state, jnp.asarray(hi0), jnp.asarray(lo0), fp_bits=FP,
        backend="jnp")
    print(json.dumps({
        "first_deferred": first_deferred,
        "first_ovf": first_ovf,
        "ok_and_deferred": both,
        "drained": not bool(d.any()),
        "rounds": rounds,
        "all_present": bool(np.asarray(hits).all()),
    }))
""")


def test_distributed_overflow_deferred_subprocess():
    """Routing overflow defers (never loses) keys; resubmission converges."""
    res = _run(OVERFLOW_SCRIPT)
    assert res["first_deferred"] > 0, "tiny capacity must actually overflow"
    assert res["first_deferred"] == res["first_ovf"], (
        "per-shard counters == deferred mask")
    assert not res["ok_and_deferred"], "deferred lanes are never acked"
    assert res["drained"], "resubmission makes progress every round"
    assert res["all_present"], "no key is ever dropped by routing overflow"


def test_local_shard_delete_host_explicit_n_buckets(rng):
    """Compat-shim regression: ``n_buckets`` must follow the active count,
    not the pow2 buffer rows (the single-node discipline, core/filter.py).

    Active count 48 in a 64-row buffer: hashing mod 64 probes the wrong
    buckets, so the pre-fix default silently missed most deletes."""
    keys = random_keys(rng, 120)
    hi, lo = hashing.key_to_u32_pair_np(keys)
    hi, lo = jnp.asarray(hi), jnp.asarray(lo)
    st = jf.make_state(48, 4, buffer_buckets=64)
    st, ok = jf.bulk_insert(st, hi, lo, fp_bits=16)
    assert bool(np.asarray(ok).all())

    # state carrying its active count: the default must pick it up
    sh = dist.ShardedFilterState(tables=st.table[None], n_buckets=48)
    sh2, dok = dist.local_shard_delete_host(sh, 0, hi, lo, fp_bits=16,
                                            backend="jnp")
    assert bool(np.asarray(dok).all())
    assert int(np.asarray(sh2.tables).sum()) == 0

    # legacy state (no n_buckets): explicit argument works ...
    legacy = dist.ShardedFilterState(tables=st.table[None])
    leg2, lok = dist.local_shard_delete_host(legacy, 0, hi, lo, fp_bits=16,
                                             backend="jnp", n_buckets=48)
    assert bool(np.asarray(lok).all())
    # ... while the buffer-rows fallback (the old default) probes wrong
    # buckets and misses — the behavior the fix removes for carried states.
    _, bad = dist.local_shard_delete_host(legacy, 0, hi, lo, fp_bits=16,
                                          backend="jnp")
    assert not bool(np.asarray(bad).all())


def test_sharded_state_compat(rng):
    """Old construction patterns keep working: bare tables, _replace-based
    host swap, and the lookup path over a stash-less state."""
    tables = jnp.zeros((2, 32, 4), jnp.uint32)
    st = dist.ShardedFilterState(tables=tables)
    assert st.stashes is None and st.n_buckets is None
    st2 = dist.local_shard_insert_host(st, 1, jnp.ones((32, 4), jnp.uint32))
    assert int(np.asarray(st2.tables[1]).sum()) == 32 * 4
    assert st2.stashes is None

    full = dist.make_sharded_state(2, 32, 4, stash_slots=16,
                                   buffer_buckets=64)
    assert full.tables.shape == (2, 64, 4)
    assert full.stashes.shape == (2, 2, 16)
    assert full.n_buckets == 32
    swapped = dist.local_shard_insert_host(full, 0,
                                           jnp.zeros((64, 4), jnp.uint32))
    assert swapped.stashes is not None and swapped.n_buckets == 32


PUMP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import distributed as dist, hashing
    from repro.serving.scheduler import DeferredWritePump

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("data",))
    NB, BS, FP = 256, 4, 16
    rng = np.random.RandomState(7)
    keys = np.unique(rng.randint(1, 2**63, size=1024, dtype=np.int64)
                     ).astype(np.uint64)
    hi, lo = hashing.key_to_u32_pair_np(keys)

    # --- valid-mask semantics: poisoned invalid lanes must be inert ----
    state = dist.make_sharded_state(2, NB, BS, stash_slots=64)
    n = 64
    vhi = jnp.concatenate([jnp.asarray(hi[:n]), jnp.zeros((n,), jnp.uint32)])
    vlo = jnp.concatenate([jnp.asarray(lo[:n]), jnp.zeros((n,), jnp.uint32)])
    valid = jnp.concatenate([jnp.ones((n,), bool), jnp.zeros((n,), bool)])
    state, ok, dfr, _ = dist.distributed_insert(
        mesh, "data", state, vhi, vlo, fp_bits=FP, valid=valid)
    ok, dfr = np.asarray(ok), np.asarray(dfr)
    zhit, _ = dist.distributed_lookup(
        mesh, "data", state, jnp.zeros((2,), jnp.uint32),
        jnp.zeros((2,), jnp.uint32), fp_bits=FP)
    mask_ok = bool(ok[:n].all() and not ok[n:].any() and not dfr.any())
    live = int(np.asarray(state.tables != 0).sum())

    # --- pump: skewed burst under tight capacity defers, then drains ---
    owner = np.asarray(hashing.owner_shard_np(hi, lo, 2))
    hot = keys[owner == 0]
    skew = np.concatenate([hot, hot, keys[owner == 1]])[:512]
    shi, slo = hashing.key_to_u32_pair_np(skew)
    pump = DeferredWritePump(mesh, "data",
                             dist.make_sharded_state(2, NB, BS,
                                                     stash_slots=64),
                             fp_bits=FP, capacity_factor=0.25)
    sok, sdfr = pump.submit(shi, slo)
    first_deferred = int(sdfr.sum())

    # hold the gate shut for 3 ticks, then open: held_ticks must count
    class Gate:
        def __init__(self, closed): self.closed, self.tripped = closed, True
        def peek(self):
            self.closed -= 1
            self.tripped = self.closed >= 0
            return not self.tripped
    pump.admission = Gate(3)
    pump.run_until_drained(max_ticks=64,
                           on_held=lambda p: None)   # keep ticking
    phits, _ = dist.distributed_lookup(
        mesh, "data", pump.state, jnp.asarray(shi), jnp.asarray(slo),
        fp_bits=FP)
    pzero, _ = dist.distributed_lookup(
        mesh, "data", pump.state, jnp.zeros((2,), jnp.uint32),
        jnp.zeros((2,), jnp.uint32), fp_bits=FP)

    print(json.dumps({
        "mask_ok": mask_ok,
        "zero_hit": bool(np.asarray(zhit).any()),
        "live": live, "n": n,
        "first_deferred": first_deferred,
        "held_ticks": pump.stats.held_ticks,
        "pending": pump.pending,
        "inserted": pump.stats.inserted,
        "submitted": pump.stats.submitted,
        "all_present": bool(np.asarray(phits).all()),
        "pad_hit": bool(np.asarray(pzero).any()),
    }))
""")


def test_deferred_write_pump_subprocess():
    """PR-7 satellite: the hysteresis-gated pump re-lands every deferred
    lane, valid-mask padding stays inert, and a closed admission gate is
    counted as held ticks instead of hammering the mesh."""
    res = _run(PUMP_SCRIPT)
    # lane-mask contract: invalid lanes are never acked, deferred, or
    # written — the all-zero poison key must not become resident
    assert res["mask_ok"], "valid mask acks exactly the valid lanes"
    assert not res["zero_hit"], "invalid poison lanes must never land"
    assert res["live"] == res["n"], "one live entry per valid lane"
    # pump contract
    assert res["first_deferred"] > 0, "tight capacity must defer"
    assert res["held_ticks"] == 3, "closed gate ticks are counted, not spun"
    assert res["pending"] == 0, "pump drains once the gate opens"
    assert res["inserted"] == res["submitted"]
    assert res["all_present"], "every deferred key eventually lands"
    assert not res["pad_hit"], "resubmission padding lanes must stay inert"


@pytest.mark.parametrize("route", ["key", "pair"])
def test_routed_delete_steps_match_the_exchange(rng, route):
    """The host's count of a routed delete's shard-local grid steps agrees
    with the lanes the program's own routing hands each owner: the valid
    rows of every source's send buffers, one ``live_steps`` count per owner
    at the delete's block, the busiest owner's taken."""
    from repro.kernels import ops as kops
    from repro.kernels.delete import live_steps
    n_shards, per, nb = 4, 2048, 1 << 16     # a 1 MiB shard: block 128
    state = dist.make_sharded_state(n_shards, nb, 4, stash_slots=8)
    hi, lo = hashing.key_to_u32_pair_np(random_keys(rng, n_shards * per))
    valid = rng.rand(hi.size) < 0.9
    cap = int(per * 2.0 / n_shards + 1)
    recv = np.zeros((n_shards, n_shards, cap), bool)  # [owner, source, slot]
    for s in range(n_shards):
        sl = slice(s * per, (s + 1) * per)
        dst, rank, fits = dist._route(jnp.asarray(hi[sl]), jnp.asarray(lo[sl]),
                                      n_shards, cap, jnp.asarray(valid[sl]),
                                      route=route, n_buckets=nb, fp_bits=16)
        recv[:, s] = np.asarray(dist._scatter_routed(
            dst, rank, fits, n_shards, cap, jnp.asarray(hi[sl]),
            jnp.asarray(lo[sl]))[2])
    block = kops.delete_block(n_shards * cap, table_bytes=nb * 4 * 4)
    assert block == 128
    pad = -(n_shards * cap) % block
    want = max(int(live_steps(np.pad(r.reshape(-1), (0, pad)), block).sum())
               for r in recv)
    assert want < -(-n_shards * cap // block)        # padding steps skipped
    assert dist.routed_delete_steps(state, hi, lo, valid, n_shards,
                                    fp_bits=16, backend="pallas",
                                    route=route) == want
    assert dist.routed_delete_steps(state, hi, lo, valid, n_shards,
                                    fp_bits=16, backend="jnp",
                                    route=route) == 0
