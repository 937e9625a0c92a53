"""Elastic resharding: zero-downtime shard split/merge + mesh rebuild.

Two layers live here:

**Mesh elasticity** (``largest_mesh`` / ``reshard_state``): when a pod drops
out, pick the largest grid the survivors support and re-derive shardings
from the logical specs; meshes come from the one helper,
``launch.mesh.make_mesh``.

**Filter elasticity** (``split_state`` / ``merge_state`` / the round
machinery): grow or shrink a live ``ShardedFilterState`` between pow2 shard
counts with NO keystore round-trip and NO rebuild.  This leans on the
partial-key cuckoo identity (Fan et al., via Eppstein's *Simplification and
Analysis*): a resident slot stores (bucket, fingerprint), and since the
candidate pair satisfies ``i + alt(i, fp) ≡ H(fp) (mod n_buckets)``, the
invariant ``min(bucket, alt(bucket, fp))`` + fingerprint identifies the
key's bucket *pair* from either end.  ``hashing.owner_shard_pair`` hashes
exactly that pair identity, so ownership under ANY shard count is
re-derivable from what the table already stores — the property key-hash
routing can never have (the key is gone).  States that want to reshard must
therefore be written with ``route="pair"`` (``core.distributed``).

Because the pair hash is independent of the shard count, owners nest across
pow2 counts: ``owner(2n) mod n == owner(n)``.  A 2x split moves a strict
subset of each shard's entries to its image shard (``s -> s + n``); a merge
folds ``s + n`` back onto ``s``.  Splits therefore never overfill (each
destination bucket receives at most one source bucket's slots); merges can
contend, so received entries run the real pair insert — place / alternate /
bounded eviction chain (kicks preserve the pair invariant) / stash spill.

Migration is the same capacity-bounded ``all_to_all`` idiom as
``distributed_insert``: each round, every shard extracts its foreign-owned
lanes (table slots + stash entries), ranks them with ``conflict_waves``
against the destination, ships ``(fingerprint, bucket)`` pairs — 8 bytes a
key, no keys — clears ONLY the lanes that fit this round at the source, and
pair-inserts what it received.  A host loop streams rounds until no foreign
lanes remain; entries never exist in zero or two places, so a lookup racing
the migration on either mesh misses only keys mid-flight in the current
round — the window the serving layer covers by parking writes in
``DeferredWritePump`` and replaying them after cutover
(``ElasticController``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import filter as jfilter
from repro.core import hashing
from repro.core.distributed import ShardedFilterState, _shard_map_unchecked
from repro.core.scheduling import conflict_waves
from repro.distributed.sharding import ParallelConfig, make_shardings
from repro.kernels import stash as kstash
from repro.launch.mesh import make_mesh


# ------------------------------------------------------- mesh elasticity --


def largest_mesh(devices: Optional[Sequence] = None, *, model_parallel: int,
                 axis_names=("data", "model")) -> Mesh:
    """Largest (data, model) mesh on the surviving devices.

    Keeps TP fixed (weights must still fit) and gives every remaining
    multiple of ``model_parallel`` devices to data parallelism.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    data = n // model_parallel
    if data < 1:
        raise RuntimeError(
            f"{n} devices cannot host model_parallel={model_parallel}")
    use = devices[: data * model_parallel]
    return make_mesh((data, model_parallel), axis_names, devices=use)


def filter_mesh(n_shards: int, axis_name: str = "data",
                devices: Optional[Sequence] = None) -> Mesh:
    """1-D filter mesh over the first ``n_shards`` devices.

    The elastic controller builds the pre- and post-cutover meshes with
    this so a 2->4 split and its 4->2 inverse agree on device order.
    """
    return make_mesh((n_shards,), (axis_name,), devices=devices)


def reshard_state(state_tree, specs_tree, new_mesh: Mesh,
                  parallel: ParallelConfig):
    """Re-derive shardings from logical specs on the new mesh and move."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state_tree)
    shardings = make_shardings(new_mesh, specs_tree, shapes, parallel)
    return jax.tree.map(jax.device_put, state_tree, shardings)


# ---------------------------------------------------- filter elasticity --


def insert_pairs(table, stash, bucket, fp, valid, *, n_buckets,
                 max_disp: int = 64):
    """Insert migrated (bucket, fingerprint) pairs into one shard's slice.

    The receive side of a migration round: lanes carry a *pair identity*
    (any bucket of the pair — the involution recovers the other), not a
    key, so this runs ``i1 = bucket mod n_buckets``, ``i2 = alt(i1, fp)``
    straight into the sequential insert core the single-node scan path uses
    (place / alternate / bounded eviction with lossless rollback), spilling
    exhausted chains to the shard stash exactly like the routed write path.
    Returns ``(table, stash, ok bool[N])``; invalid lanes never touch
    either structure.
    """
    bucket_size = table.shape[1]
    n = jnp.asarray(n_buckets, jnp.uint32)
    b1 = bucket.astype(jnp.uint32) % n
    b2 = hashing.alt_index_dyn(b1, fp.astype(jnp.uint32), n)

    def step(carry, x):
        table, stash = carry
        f, i1, i2, v = x

        def attempt(_):
            t, ok = jfilter._insert_one(table, f, i1, i2, n_buckets,
                                        max_disp=max_disp,
                                        bucket_size=bucket_size)

            def spill(_):
                s, fits = kstash.stash_spill(
                    stash, f[None], i2[None], jnp.ones((1,), bool))
                return (t, s), fits[0]

            return jax.lax.cond(ok, lambda _: ((t, stash), ok), spill,
                                operand=None)

        return jax.lax.cond(v, attempt,
                            lambda _: ((table, stash), jnp.bool_(False)),
                            operand=None)

    (table, stash), ok = jax.lax.scan(step, (table, stash),
                                      (fp, b1, b2, valid))
    return table, stash, ok


@functools.lru_cache(maxsize=None)
def _migrate_round_fn(mesh: Mesh, axis: str, target_shards: int, cap: int,
                      n_buckets: int, max_disp: int):
    """Build (and cache) one jitted migration round over ``mesh``.

    Each shard: enumerate its lanes (every table slot with its row index,
    every stash entry with its stored bucket — the SAME pair identity),
    compute the pair owner under ``target_shards``, extract foreign lanes,
    rank them per destination with ``conflict_waves``, clear at the source
    ONLY the lanes that fit this round's ``cap`` (streaming — unmoved lanes
    survive for the next round), all_to_all the (fp, bucket) buffers, and
    pair-insert the received lanes.  Returns per-shard
    ``(tables, stashes, moved, remaining, failed)`` where ``remaining``
    counts foreign lanes still resident (the host loop's stop condition)
    and ``failed`` counts received lanes that neither placed nor spilled —
    real capacity loss the caller must surface.
    """
    n_mesh = mesh.shape[axis]

    def shard_fn(tables, stashes):
        table, stash = tables[0], stashes[0]
        me = jax.lax.axis_index(axis).astype(jnp.int32)
        buf, bucket_size = table.shape
        n_table = buf * bucket_size

        t_fp = table.reshape(-1)
        t_bkt = jnp.repeat(
            jnp.arange(buf, dtype=jnp.uint32), bucket_size)
        lane_fp = jnp.concatenate([t_fp, stash[0]])
        lane_bkt = jnp.concatenate([t_bkt, stash[1]])
        occupied = lane_fp != 0
        owner = hashing.owner_shard_pair(
            lane_bkt, lane_fp, n_buckets, target_shards).astype(jnp.int32)
        foreign = occupied & (owner != me)

        rank = conflict_waves(owner, foreign)
        fits = (rank < cap) & foreign
        dst = jnp.where(fits, owner, n_mesh)

        # Clear shipped lanes at the source BEFORE inserting received ones,
        # so a shard that both sends and receives reuses the freed slots.
        new_table = jnp.where(fits[:n_table], jnp.uint32(0),
                              t_fp).reshape(buf, bucket_size)
        s_clear = fits[n_table:]
        new_stash = jnp.stack([jnp.where(s_clear, jnp.uint32(0), stash[0]),
                               jnp.where(s_clear, jnp.uint32(0), stash[1])])

        buf_fp = jnp.zeros((n_mesh, cap), jnp.uint32).at[dst, rank].set(
            lane_fp, mode="drop")
        buf_bkt = jnp.zeros((n_mesh, cap), jnp.uint32).at[dst, rank].set(
            lane_bkt, mode="drop")
        buf_valid = jnp.zeros((n_mesh, cap), jnp.bool_).at[dst, rank].set(
            fits, mode="drop")
        r_fp = jax.lax.all_to_all(buf_fp, axis, 0, 0, tiled=False)
        r_bkt = jax.lax.all_to_all(buf_bkt, axis, 0, 0, tiled=False)
        r_valid = jax.lax.all_to_all(buf_valid, axis, 0, 0, tiled=False)

        new_table, new_stash, ok = insert_pairs(
            new_table, new_stash, r_bkt.reshape(-1), r_fp.reshape(-1),
            r_valid.reshape(-1), n_buckets=n_buckets, max_disp=max_disp)

        moved = jnp.sum(fits, dtype=jnp.int32)
        remaining = jnp.sum(foreign & ~fits, dtype=jnp.int32)
        failed = jnp.sum(r_valid.reshape(-1) & ~ok, dtype=jnp.int32)
        return (new_table[None], new_stash[None], moved[None],
                remaining[None], failed[None])

    mapped = _shard_map_unchecked(
        shard_fn, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(axis),) * 5)
    return jax.jit(mapped)


@dataclasses.dataclass
class MigrationReport:
    """What one split/merge did — the recovery-metrics payload."""
    direction: str          # "split" | "merge"
    old_shards: int
    new_shards: int
    keys_moved: int         # fingerprints shipped shard-to-shard
    rounds: int             # all_to_all rounds until drained
    failed: int             # received lanes lost to full destinations
    seconds: float = 0.0    # migration wall time (filled by split/merge)


def migrate_state(mesh: Mesh, axis: str, state: ShardedFilterState, *,
                  target_shards: int, cap: Optional[int] = None,
                  max_disp: int = 64, max_rounds: int = 64):
    """Stream every mis-owned lane to its pair owner under ``target_shards``.

    The shared engine under ``split_state``/``merge_state``: runs jitted
    migration rounds on ``mesh`` until no shard holds a foreign lane.
    ``cap`` bounds fingerprints per (src, dst) pair per round — the default
    moves everything a shard can hold in one round; tests shrink it to
    exercise multi-round streaming.  Requires per-shard stashes (receivers
    spill contended chains exactly like the routed write path; silently
    dropping them would lose keys).

    Returns ``(new_state, moved, rounds, failed)``.
    """
    assert state.stashes is not None, \
        "elastic migration requires per-shard stashes (spill target)"
    n_buckets = (state.n_buckets if state.n_buckets is not None
                 else state.tables.shape[1])
    bucket_size = state.tables.shape[2]
    stash_slots = state.stashes.shape[2]
    if cap is None:
        cap = n_buckets * bucket_size + stash_slots
    fn = _migrate_round_fn(mesh, axis, target_shards, cap, n_buckets,
                           max_disp)
    tables, stashes = state.tables, state.stashes
    moved_total = rounds = failed_total = 0
    while True:
        tables, stashes, moved, remaining, failed = fn(tables, stashes)
        rounds += 1
        moved_total += int(jnp.sum(moved))
        failed_total += int(jnp.sum(failed))
        if int(jnp.sum(remaining)) == 0:
            break
        if rounds >= max_rounds:
            raise RuntimeError(
                f"migration did not drain in {max_rounds} rounds "
                f"({int(jnp.sum(remaining))} lanes still foreign)")
    new_state = state._replace(tables=tables, stashes=stashes)
    return new_state, moved_total, rounds, failed_total


def split_state(new_mesh: Mesh, axis: str, state: ShardedFilterState, *,
                cap: Optional[int] = None, max_disp: int = 64,
                max_rounds: int = 64
                ) -> tuple[ShardedFilterState, MigrationReport]:
    """Grow a pair-routed state 2x: n shards -> 2n, live, rebuild-free.

    Seeds the new mesh hierarchically — shard ``s < n`` keeps the old shard
    ``s``'s slice, shards ``n..2n-1`` start empty — then migrates on the NEW
    mesh.  The pow2 owner hierarchy (``owner(2n) mod n == owner(n)``) means
    every foreign lane on shard ``s`` is bound for exactly ``s + n``, and a
    destination bucket receives at most one source bucket's slots: splits
    cannot overfill and every received lane places without eviction.
    """
    n_old = state.tables.shape[0]
    n_new = new_mesh.shape[axis]
    assert n_new == 2 * n_old, (n_old, n_new)
    assert n_new & (n_new - 1) == 0, "shard counts must stay pow2"
    t0 = time.perf_counter()
    pad_t = jnp.zeros((n_new - n_old,) + state.tables.shape[1:], jnp.uint32)
    pad_s = jnp.zeros((n_new - n_old,) + state.stashes.shape[1:], jnp.uint32)
    place = jax.sharding.NamedSharding(new_mesh, P(axis))
    seeded = state._replace(
        tables=jax.device_put(jnp.concatenate([state.tables, pad_t]), place),
        stashes=jax.device_put(jnp.concatenate([state.stashes, pad_s]),
                               place))
    new_state, moved, rounds, failed = migrate_state(
        new_mesh, axis, seeded, target_shards=n_new, cap=cap,
        max_disp=max_disp, max_rounds=max_rounds)
    jax.block_until_ready(new_state.tables)
    return new_state, MigrationReport(
        "split", n_old, n_new, moved, rounds, failed,
        time.perf_counter() - t0)


def merge_state(old_mesh: Mesh, axis: str, state: ShardedFilterState, *,
                cap: Optional[int] = None, max_disp: int = 64,
                max_rounds: int = 64
                ) -> tuple[ShardedFilterState, MigrationReport]:
    """Shrink a pair-routed state 2x: n shards -> n/2, live, rebuild-free.

    Migrates on the OLD mesh with the halved owner function — the top half's
    entries all fold onto their image shard ``s - n/2`` — then slices the
    drained top half off.  Receivers are genuinely contended here (two
    shards' entries interleave into one), which is why received lanes run
    the full pair insert with eviction chains and stash spill.
    """
    n_old = state.tables.shape[0]
    assert n_old == old_mesh.shape[axis] and n_old % 2 == 0
    k = n_old // 2
    t0 = time.perf_counter()
    new_state, moved, rounds, failed = migrate_state(
        old_mesh, axis, state, target_shards=k, cap=cap, max_disp=max_disp,
        max_rounds=max_rounds)
    top_tables = int(jnp.sum(new_state.tables[k:] != 0))
    top_stash = int(jnp.sum(new_state.stashes[k:, 0, :] != 0))
    assert top_tables == 0 and top_stash == 0, \
        f"merge left {top_tables}+{top_stash} lanes on drained shards"
    # Host round-trip the sliced halves: the result is uncommitted, so the
    # caller's k-shard mesh (unknown here) can place it without a device
    # conflict — control-plane cost, once per merge.
    merged = new_state._replace(
        tables=jnp.asarray(np.asarray(new_state.tables[:k])),
        stashes=jnp.asarray(np.asarray(new_state.stashes[:k])))
    jax.block_until_ready(merged.tables)
    return merged, MigrationReport(
        "merge", n_old, k, moved, rounds, failed, time.perf_counter() - t0)


# --------------------------------------------------- serving control plane


@dataclasses.dataclass
class ElasticController:
    """Zero-downtime split/merge over a live ``DeferredWritePump``.

    The cutover protocol: (1) hold the pump — fresh submits park instead of
    racing the migration — and freeze write admission; (2) run the
    migration (split on the new mesh / merge on the old); (3) retarget the
    pump at the new (mesh, state) and release; (4) drain the parked backlog
    through the normal resubmission path.  Time-to-recover is hold ->
    backlog-drained, the recovery metric the bench gate enforces.

    ``recovery`` is an ``obs.recovery.RecoveryMetrics`` (optional — without
    one the controller is metrics-silent, matching the repo-wide contract).
    """

    pump: object                               # serving DeferredWritePump
    axis: str = "data"
    recovery: Optional[object] = None
    cap: Optional[int] = None
    max_disp: int = 64
    max_rounds: int = 64
    drain_ticks: int = 100
    clock: Callable[[], float] = time.perf_counter

    def split(self, new_mesh: Mesh) -> MigrationReport:
        return self._resize("split", new_mesh)

    def merge(self, new_mesh: Mesh) -> MigrationReport:
        return self._resize("merge", new_mesh)

    def _resize(self, direction: str, new_mesh: Mesh) -> MigrationReport:
        pump, rec = self.pump, self.recovery
        t0 = self.clock()
        pump.hold()
        admission = getattr(pump, "admission", None)
        if admission is not None and hasattr(admission, "freeze"):
            admission.freeze()
        try:
            with (rec.span(f"elastic_{direction}",
                           new_shards=new_mesh.shape[self.axis])
                  if rec else _NULL_CTX):
                if direction == "split":
                    new_state, report = split_state(
                        new_mesh, self.axis, pump.state, cap=self.cap,
                        max_disp=self.max_disp, max_rounds=self.max_rounds)
                else:
                    # merge migrates on the OLD mesh, then lands on the new.
                    new_state, report = merge_state(
                        pump.mesh, self.axis, pump.state, cap=self.cap,
                        max_disp=self.max_disp, max_rounds=self.max_rounds)
                pump.retarget(new_mesh, self.axis, new_state)
        finally:
            if admission is not None and hasattr(admission, "thaw"):
                admission.thaw()
            pump.release()
        backlog = pump.pending
        pump.run_until_drained(max_ticks=self.drain_ticks)
        seconds = self.clock() - t0
        if rec is not None:
            rec.migration(direction, keys=report.keys_moved,
                          rounds=report.rounds, failed=report.failed,
                          seconds=report.seconds)
            rec.backlog(pump.pending)
            rec.drained(backlog - pump.pending)
            rec.recovered(f"elastic_{direction}", seconds)
        return report


class _Null:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _Null()
