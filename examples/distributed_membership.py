"""Distributed membership service: OCF shards on a JAX mesh (paper §I-B).

The paper's Cassandra-cluster scenario: keys are owned by shards; batched
inserts, lookups, and verified deletes are all routed shard-to-shard with
one capacity-bounded all_to_all and run by the owner's local data plane —
writes resolve their eviction chains and stash spills on-device inside
shard_map (PR 6), no host round-trips.  Run on 8 virtual devices:

    PYTHONPATH=src python examples/distributed_membership.py
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as dist
from repro.core import hashing
from repro.launch.mesh import make_mesh

N_SHARDS, N_BUCKETS = 8, 4096

mesh = make_mesh((N_SHARDS,), ("data",))
rng = np.random.RandomState(0)
keys = rng.randint(0, 2 ** 63, size=32768, dtype=np.int64).astype(np.uint64)
hi, lo = hashing.key_to_u32_pair_np(keys)

# Routed insert: every key rides the all_to_all to its owner shard, which
# runs the conflict-aware scheduled insert on its table slice on-device —
# the host never partitions keys or swaps tables (that was the pre-PR-6
# idiom; see ARCHITECTURE.md "Distributed write path").
owner = np.asarray(hashing.owner_shard_np(hi, lo, N_SHARDS))
state = dist.make_sharded_state(N_SHARDS, N_BUCKETS, 4)
state, ok, deferred, iov = dist.distributed_insert(
    mesh, "data", state, jnp.asarray(hi), jnp.asarray(lo), fp_bits=16)
assert bool(np.asarray(ok).all()) and not bool(np.asarray(deferred).any())
print(f"{N_SHARDS} shards, {keys.size} keys routed+inserted on-device, "
      f"owner histogram: {np.bincount(owner, minlength=N_SHARDS)}")
print(f"aggregate load: {float(dist.sharded_occupancy(state)):.3f}")

# Distributed lookup: one all_to_all out, local probe, one all_to_all back.
hits, overflow = dist.distributed_lookup(
    mesh, "data", state, jnp.asarray(hi), jnp.asarray(lo), fp_bits=16)
print(f"present keys found: {int(np.asarray(hits).sum())}/{keys.size}")
print(f"per-shard routing overflow: {np.asarray(overflow)}")

absent = rng.randint(0, 2 ** 63, size=32768, dtype=np.int64).astype(np.uint64)
ahi, alo = hashing.key_to_u32_pair_np(absent)
ahits, _ = dist.distributed_lookup(mesh, "data", state, jnp.asarray(ahi),
                                   jnp.asarray(alo), fp_bits=16)
print(f"false positives on {absent.size} absent keys: "
      f"{int(np.asarray(ahits).sum())}")

# Congestion: shrink routing capacity -> overflow counters fire (the EOF
# signal) while answers stay conservative.
thits, tov = dist.distributed_lookup(mesh, "data", state, jnp.asarray(hi),
                                     jnp.asarray(lo), fp_bits=16,
                                     capacity_factor=0.5)
print(f"tight capacity: found={int(np.asarray(thits).sum())}/{keys.size} "
      f"overflow={np.asarray(tov)} (burst signal -> EOF controller)")

# Routed verified delete: half the keys churn out, owner shards clear them
# (table first, then any stash-parked copies) in the same dispatch shape.
half = keys.size // 2
state, dok, _, _ = dist.distributed_delete(
    mesh, "data", state, jnp.asarray(hi[:half]), jnp.asarray(lo[:half]),
    fp_bits=16)
rhits, _ = dist.distributed_lookup(mesh, "data", state, jnp.asarray(hi),
                                   jnp.asarray(lo), fp_bits=16)
print(f"deleted {int(np.asarray(dok).sum())}/{half}; survivors found: "
      f"{int(np.asarray(rhits)[half:].sum())}/{keys.size - half}, "
      f"load now {float(dist.sharded_occupancy(state)):.3f}")

# Deferred-batch resubmission (PR 7): a skewed burst under tight routing
# capacity overflows some owners' all_to_all rows — those lanes come back
# as a DEFERRED batch, never attempted.  The pump parks them and re-offers
# under the admission controller's hysteresis, so resubmission waits out
# shard congestion instead of hammering saturated owners.
from repro.serving.scheduler import DeferredWritePump

burst = rng.randint(0, 2 ** 63, size=8192, dtype=np.int64).astype(np.uint64)
bhi, blo = hashing.key_to_u32_pair_np(burst)
# Skew: half the burst targets two hot owners (replayed hot-key pattern).
hot = np.asarray(hashing.owner_shard_np(bhi, blo, N_SHARDS)) < 2
skewed = np.concatenate([burst[hot], burst[hot], burst[~hot]])[:8192]
shi, slo = hashing.key_to_u32_pair_np(skewed)

pump = DeferredWritePump(mesh, "data",
                         dist.make_sharded_state(N_SHARDS, N_BUCKETS, 4),
                         fp_bits=16, capacity_factor=0.5)
ok, deferred = pump.submit(shi, slo)
print(f"\nburst of {skewed.size} under tight capacity: "
      f"{int(ok.sum())} landed, {int(deferred.sum())} deferred")
pump.run_until_drained(max_ticks=64)
print(f"pump drained: inserted={pump.stats.inserted} "
      f"resubmitted={pump.stats.resubmitted} held_ticks="
      f"{pump.stats.held_ticks} pending={pump.pending} "
      f"(signal={pump.admission.signal():.2f})")
phits, _ = dist.distributed_lookup(mesh, "data", pump.state,
                                   jnp.asarray(shi), jnp.asarray(slo),
                                   fp_bits=16)
assert bool(np.asarray(phits).all()), "a deferred key never landed"
print("every burst key resident after hysteresis-gated resubmission")
