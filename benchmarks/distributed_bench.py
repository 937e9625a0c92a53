"""Distributed write-path benchmark: routed vs host-loop sharded writes.

Needs four devices.  On CPU the forced host device count must be exported
before jax initializes, so ``filter_bench.distributed_rows`` runs this file
in a subprocess and merges the JSON printed on the last stdout line; on an
accelerator it calls ``run()`` in its own process.

Two arms per op, timed interleaved from the same preloaded ~0.8-load base
state (both run the identical per-shard kernel, so the delta is pure
dispatch architecture):

* ``distributed_insert_pallas`` / ``distributed_delete_pallas`` — the PR-6
  routed path: capacity-bounded all_to_all to the owner shard, conflict-
  aware scheduled insert / fused delete inside ``shard_map``, per-shard
  stashes.  Zero host round-trips, zero whole-stack copies in the loop.

* ``distributed_insert_hostloop`` / ``distributed_delete_hostloop`` — the
  pre-PR-6 idiom this PR retires: partition keys by owner on the host,
  loop over shards running the single-shard op, swap each mutated table
  back with ``local_shard_*_host`` (a stacked-buffer copy per shard per
  batch).

The timed batch lands on a ~0.9-load table, so the eviction machinery and
stash spill are on the clock — the contended regime the paper's burst
story cares about.
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import json  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from filter_bench import _interleaved_times  # noqa: E402
from repro.core import distributed as dist  # noqa: E402
from repro.core import hashing  # noqa: E402
from repro.core.filter_ops import FilterOps  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

N_SHARDS = 4
N_BUCKETS = 1024                     # per shard -> 16384 slots total
PRELOAD = 12800                      # ~0.78 load before the timed batch
BATCH = 2048                         # timed batch -> ~0.9 load
EVICT_ROUNDS = 64
STASH_SLOTS = 256
FP = 16


def _pair(rng, n):
    keys = rng.randint(0, 2 ** 63, size=n, dtype=np.int64).astype(np.uint64)
    hi, lo = hashing.key_to_u32_pair_np(keys)
    return hi, lo


def run() -> dict:
    mesh = make_mesh((N_SHARDS,), ("data",))
    rng = np.random.RandomState(42)
    phi, plo = _pair(rng, PRELOAD)
    bhi, blo = _pair(rng, BATCH)
    owner = np.asarray(hashing.owner_shard_np(bhi, blo, N_SHARDS))
    jhi, jlo = jnp.asarray(bhi), jnp.asarray(blo)

    base = dist.make_sharded_state(N_SHARDS, N_BUCKETS, 4,
                                   stash_slots=STASH_SLOTS)
    base, ok, _, _ = dist.distributed_insert(
        mesh, "data", base, jnp.asarray(phi), jnp.asarray(plo), fp_bits=FP,
        backend="pallas", evict_rounds=EVICT_ROUNDS)
    jax.block_until_ready(base.tables)
    preload_load = float(dist.sharded_occupancy(base))

    fops = FilterOps(fp_bits=FP, backend="pallas",
                     evict_rounds=EVICT_ROUNDS, schedule=True)
    per_shard = [(jnp.asarray(bhi[owner == s]), jnp.asarray(blo[owner == s]))
                 for s in range(N_SHARDS)]

    def routed_insert():
        st, ok, _, _ = dist.distributed_insert(
            mesh, "data", base, jhi, jlo, fp_bits=FP, backend="pallas",
            evict_rounds=EVICT_ROUNDS)
        return st.tables

    def hostloop_insert():
        # pre-PR-6: host partition + per-shard op + whole-stack swap
        st = base
        for s in range(N_SHARDS):
            shi, slo = per_shard[s]
            tbl, stash, ok = fops.insert_table(st.tables[s], shi, slo,
                                               stash=st.stashes[s])
            st = dist.local_shard_insert_host(st, s, tbl)
            st = st._replace(stashes=st.stashes.at[s].set(stash))
        return st.tables

    def routed_delete():
        st, ok, _, _ = dist.distributed_delete(
            mesh, "data", loaded, jhi, jlo, fp_bits=FP, backend="pallas")
        return st.tables

    def hostloop_delete():
        st = loaded
        for s in range(N_SHARDS):
            shi, slo = per_shard[s]
            st, ok = dist.local_shard_delete_host(st, s, shi, slo,
                                                  fp_bits=FP,
                                                  backend="pallas")
        return st.tables

    # the delete arms run against the post-batch ~0.9-load state
    loaded, lok, _, _ = dist.distributed_insert(
        mesh, "data", base, jhi, jlo, fp_bits=FP, backend="pallas",
        evict_rounds=EVICT_ROUNDS)
    jax.block_until_ready(loaded.tables)
    final_load = float(dist.sharded_occupancy(loaded))

    best = _interleaved_times({
        "insert_pallas": routed_insert,
        "insert_hostloop": hostloop_insert,
        "delete_pallas": routed_delete,
        "delete_hostloop": hostloop_delete,
    }, reps=2, trials=5)

    results = {"distributed_n_shards": N_SHARDS,
               "distributed_batch": BATCH,
               "distributed_preload_load": round(preload_load, 4),
               "distributed_batch_load": round(final_load, 4),
               "distributed_batch_ok": int(np.asarray(lok).sum()),
               "distributed_stash_spilled": int(
                   np.asarray(loaded.stashes[:, 0, :] != 0).sum())}
    for name, t in best.items():
        results[f"distributed_{name}_keys_per_s"] = int(BATCH / t)
        results[f"distributed_{name}_us_per_key"] = round(t / BATCH * 1e6, 3)
    return results


if __name__ == "__main__":
    print(json.dumps(run()))
