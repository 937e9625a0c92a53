"""Distributed OCF — the paper's distributed-database story on a JAX mesh.

Filter shards live along a mesh axis (one shard per `data`-axis slice, the
same placement a Cassandra node ring would have).  Lookups AND writes are
routed with the MoE dispatch shape:

    owner = H(key) mod n_shards
    one capacity-bounded all_to_all sends each key to its owner shard,
    the owner runs the local data-plane op (probe / scheduled insert /
    fused delete) on its table slice,
    a second all_to_all returns the answers.

The routing rank is ``core.scheduling.conflict_waves`` with the owner shard
as the "bucket": lane i claims slot ``wave[i]`` of its owner's row in the
send buffer, and ``wave >= cap`` IS the routing-overflow condition — the
same definition the insert kernels use for conflict-free wave dispatch.

Burst tolerance shows up here exactly as in the paper: the per-shard routing
capacity is a buffer; ``overflow`` counts keys that exceeded it and feeds
the EOF congestion signal, the same way switch-queue marking drives the
resize controller.  Lookup answers overflowed keys conservatively ("maybe
present"); writes return them as a **deferred batch** (never attempted —
resubmit next step), so routing pressure degrades latency, never
correctness.

Writes are the PR-6 tentpole: ``distributed_insert`` / ``distributed_delete``
run the PR-5 conflict-aware scheduled insert — bounded eviction chains,
spill to a per-shard device-resident stash, fused verified delete —
entirely inside ``shard_map``.  Per-shard stashes ride in
``ShardedFilterState`` next to the tables, and the enclosing jit donates
both stacks, so the hot loop never copies a table and never bounces one
through the host (the pre-PR-6 ``local_shard_*_host`` swap functions remain
only as control-plane compat shims for rebuilds).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import filter as jfilter
from repro.core import hashing
from repro.core.filter_ops import FilterOps
from repro.core.scheduling import conflict_waves
from repro.kernels import ops as kops
from repro.kernels.delete import live_steps
from repro.kernels.stash import DEFAULT_STASH_SLOTS

def _shard_map_for(backend: str, fn, *, mesh, in_specs, out_specs):
    """shard_map wrapper that disables the replication check for kernels.

    shard_map's replication checker has no rule for ``pallas_call`` (the
    reason the Pallas shard probe used to be impossible — ROADMAP item);
    with fully explicit out_specs the check is advisory here, so it is
    dropped exactly when the FilterOps dispatch may lower a kernel.
    """
    if backend == "jnp":
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)
    return _shard_map_unchecked(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs)


def _shard_map_unchecked(fn, *, mesh, in_specs, out_specs):
    """shard_map with the replication check off on every backend.

    The routed *writes* need this even on the jnp arm: their eviction scan
    lowers to ``lax.while``, which the checker has no rule for either.
    Out_specs are fully explicit, so the check is advisory here too.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class ShardedFilterState(NamedTuple):
    """Per-shard filter data plane, stacked along the shard axis.

    ``tables``: uint32[n_shards, buffer_buckets, bucket_size].
    ``stashes``: uint32[n_shards, 2, stash_slots] overflow stashes (one per
    shard, mutated on-device by the routed writes), or None for read-only /
    pre-PR-6 states — every entry point treats a stash-less state as
    "no spill, chain exhaustion fails the lane".
    ``n_buckets``: the shards' ACTIVE bucket count as a static python int
    (every shard resizes in lockstep — the controller owns rotation), or
    None meaning "the full buffer" (tables.shape[1]).  Static on purpose:
    it is a kernel grid parameter inside shard_map, and the pow2 buffer
    discipline (core/filter.py) makes recompiles rare.
    """
    tables: jax.Array
    stashes: Optional[jax.Array] = None
    n_buckets: Optional[int] = None


def make_sharded_state(n_shards: int, n_buckets: int, bucket_size: int = 4,
                       *, stash_slots: int = DEFAULT_STASH_SLOTS,
                       buffer_buckets: Optional[int] = None
                       ) -> ShardedFilterState:
    """Fresh sharded state: zero tables + per-shard overflow stashes.

    ``buffer_buckets`` preallocates the pow2 pool the single-node path uses
    (``core/filter.py``); the active count ``n_buckets`` rides in the state
    so every consumer mods by the same modulus.  ``stash_slots=0`` opts out
    of stashes (pre-PR-6 behavior: exhausted chains roll back and fail).
    """
    buf = buffer_buckets or n_buckets
    assert buf >= n_buckets
    return ShardedFilterState(
        tables=jnp.zeros((n_shards, buf, bucket_size), dtype=jnp.uint32),
        stashes=(jnp.zeros((n_shards, 2, stash_slots), dtype=jnp.uint32)
                 if stash_slots else None),
        n_buckets=n_buckets)


def sharded_occupancy(state: ShardedFilterState) -> jax.Array:
    """Aggregate load factor (live slots / capacity) -> float32[].

    Counts table residents and stash entries against table capacity — the
    quantity the bench gate's load assertion and the resize controller's
    o_max threshold both read.
    """
    live = jnp.sum(state.tables != 0)
    if state.stashes is not None:
        live = live + jnp.sum(state.stashes[:, 0, :] != 0)
    return live.astype(jnp.float32) / jnp.float32(state.tables.size)


def _capacity(n_lanes: int, n_shards: int, capacity_factor: float) -> int:
    """Slots per (source, owner) pair of a routed call of ``n_lanes``."""
    return int(n_lanes // n_shards * capacity_factor / n_shards + 1)


def _route(hi, lo, n_shards: int, cap: int, valid=None, *,
           route: str = "key", n_buckets: Optional[int] = None,
           fp_bits: Optional[int] = None):
    """Owner routing for one source shard's lane batch.

    Returns (dst int32[N] — owner or n_shards for overflow, rank int32[N]
    — the claimed slot in the owner's row, fits bool[N]).  ``rank`` is
    ``conflict_waves`` with the owner shard as the bucket, computed in
    original lane order — so answers scatter straight back by (dst, rank)
    with no argsort/inverse permutation.  Invalid lanes (``valid=False`` —
    resubmission padding) claim no capacity slot and never fit.

    ``route`` picks the owner function: ``"key"`` hashes the raw key
    (legacy, cheapest); ``"pair"`` hashes the key's candidate-pair
    invariant (min bucket + fingerprint), the routing elastic resharding
    requires — a stored slot's owner stays re-derivable after the key is
    gone (``distributed/elastic.py``).
    """
    if route == "pair":
        owner = hashing.owner_shard_key_pair(
            hi, lo, n_buckets, fp_bits, n_shards).astype(jnp.int32)
    else:
        owner = hashing.owner_shard(hi, lo, n_shards).astype(jnp.int32)
    if valid is None:
        valid = jnp.ones(owner.shape, bool)
    rank = conflict_waves(owner, valid)
    fits = (rank < cap) & valid
    dst = jnp.where(fits, owner, n_shards)
    return dst, rank, fits


def _scatter_routed(dst, rank, fits, n_shards: int, cap: int, hi, lo):
    """Lane batch -> capacity-bounded send buffers ([n_shards, cap] each)."""
    buf_hi = jnp.zeros((n_shards, cap), jnp.uint32).at[dst, rank].set(
        hi, mode="drop")
    buf_lo = jnp.zeros((n_shards, cap), jnp.uint32).at[dst, rank].set(
        lo, mode="drop")
    valid = jnp.zeros((n_shards, cap), jnp.bool_).at[dst, rank].set(
        fits, mode="drop")
    return buf_hi, buf_lo, valid


def _local_probe(table, hi, lo, fp_bits: int, backend: str = "auto"):
    """Per-shard membership probe, routed through the FilterOps data plane
    (same backend dispatch as the single-node OCF hot path)."""
    return FilterOps(fp_bits=fp_bits, backend=backend).probe_table(
        table, hi, lo)


# Bounded: elastic split/merge and recovery build new meshes, and a cache
# keyed on the Mesh keeps every retired geometry's executable alive.
@functools.lru_cache(maxsize=16)
def _lookup_fn(mesh: Mesh, axis: str, n_shards: int, cap: int, fp_bits: int,
               backend: str, n_buckets: Optional[int], has_stash: bool,
               route: str, route_nb: int):
    """Build (and cache) the jitted routed-lookup executable — one compile
    per static configuration, not one per call."""
    fops = FilterOps(fp_bits=fp_bits, backend=backend)

    # Named for the device trace: a profile tells the routed programs apart
    # by their jit names (``jit_routed_lookup`` / ``_insert`` / ``_delete``).
    def routed_lookup(tables, *args):
        # tables: [1, buf, b] local shard; hi/lo: [per_shard]
        stashes, hi, lo = args if has_stash else (None, *args)
        table = tables[0]
        stash = stashes[0] if has_stash else None
        dst, rank, fits = _route(hi, lo, n_shards, cap, route=route,
                                 n_buckets=route_nb, fp_bits=fp_bits)
        overflow = jnp.sum(~fits, dtype=jnp.int32)
        buf_hi, buf_lo, valid = _scatter_routed(dst, rank, fits, n_shards,
                                                cap, hi, lo)
        # Exchange: after all_to_all, row s holds what shard s sent me.
        r_hi = jax.lax.all_to_all(buf_hi, axis, 0, 0, tiled=False)
        r_lo = jax.lax.all_to_all(buf_lo, axis, 0, 0, tiled=False)
        r_valid = jax.lax.all_to_all(valid, axis, 0, 0, tiled=False)
        hit = fops.probe_table(table, r_hi.reshape(-1), r_lo.reshape(-1),
                               n_buckets=n_buckets, stash=stash
                               ).reshape(n_shards, cap)
        hit = jnp.where(r_valid, hit, False)
        # Route answers back; overflowed lanes answer "maybe present".
        back = jax.lax.all_to_all(hit, axis, 0, 0, tiled=False)
        ans = jnp.where(fits, back[dst.clip(0, n_shards - 1), rank], True)
        return ans, overflow[None]

    return jax.jit(_shard_map_for(
        backend, routed_lookup, mesh=mesh,
        in_specs=(P(axis),) * (4 if has_stash else 3),
        out_specs=(P(axis), P(axis))))


def distributed_lookup(mesh: Mesh, axis: str, state: ShardedFilterState,
                       hi: jax.Array, lo: jax.Array, *, fp_bits: int,
                       capacity_factor: float = 2.0, backend: str = "auto",
                       route: str = "key"):
    """Batched membership across filter shards.

    ``hi``/``lo``: uint32[n_shards * per_shard] keys, sharded over ``axis``.
    Returns (hits bool[N], overflow int32[n_shards] per-shard overflow
    count).  Overflowed keys answer True ("maybe") — conservative for
    dedup/caching, and the overflow count is the congestion signal for the
    EOF policy.  States carrying per-shard stashes answer spilled keys in
    the same fused probe pass.

    ``backend`` selects the local-probe data plane ("jnp" | "pallas" |
    "auto") inside ``shard_map`` — the same FilterOps dispatch as the
    single-node hot path: "auto" is the kernel arm on TPU and jnp
    elsewhere ("pallas" forces the kernel arm, its XLA emulation off TPU —
    how the parity tests pin it).

    ``route`` must match the routing the state was written with ("key" |
    "pair" — see ``_route``); probing a pair-routed elastic state with key
    routing sends keys to the wrong shard and silently false-negatives.
    """
    n_shards = mesh.shape[axis]
    cap = _capacity(hi.shape[0], n_shards, capacity_factor)
    has_stash = state.stashes is not None
    nb = state.n_buckets
    route_nb = nb if nb is not None else state.tables.shape[1]
    fn = _lookup_fn(mesh, axis, n_shards, cap, fp_bits, backend, nb,
                    has_stash, route, route_nb)
    if has_stash:
        return fn(state.tables, state.stashes, hi, lo)
    return fn(state.tables, hi, lo)


# ------------------------------------------------------- routed writes --
#
# One cached builder serves insert and delete: the dispatch shape (route ->
# all_to_all -> local FilterOps op -> all_to_all back) is identical; only
# the shard-local op differs.  The jit wrapping the shard_map donates the
# table/stash stacks, so XLA aliases them in->out and a write step performs
# ZERO whole-table copies and ZERO host round-trips — the acceptance bar
# the host-swap compat shims (below) could never meet.


@functools.lru_cache(maxsize=None)
def _routed_write_fn(mesh: Mesh, axis: str, op: str, n_shards: int,
                     cap: int, fp_bits: int, backend: str,
                     evict_rounds: Optional[int], max_disp: int,
                     schedule: bool, donate: bool,
                     n_buckets: Optional[int], has_stash: bool,
                     route: str, route_nb: int):
    """Build (and cache) the jitted routed-write executable.

    Cache key == every static that shapes the traced program; jax.jit
    handles retracing across batch shapes within one entry.  Donation is
    threaded HERE, at the outermost jit — inside the shard_map body the
    arrays are tracers, so inner kernel calls stay donate=False and the
    in-place update happens at this boundary (see FilterOps raw-table ops).
    """
    fops = FilterOps(fp_bits=fp_bits, backend=backend,
                     evict_rounds=evict_rounds, max_disp=max_disp,
                     schedule=schedule)

    def routed_write(tables, stashes, hi, lo, lane_valid):
        table = tables[0]
        stash = stashes[0] if has_stash else None
        dst, rank, fits = _route(hi, lo, n_shards, cap, lane_valid,
                                 route=route, n_buckets=route_nb,
                                 fp_bits=fp_bits)
        overflow = jnp.sum(~fits & lane_valid, dtype=jnp.int32)
        buf_hi, buf_lo, valid = _scatter_routed(dst, rank, fits, n_shards,
                                                cap, hi, lo)
        r_hi = jax.lax.all_to_all(buf_hi, axis, 0, 0, tiled=False)
        r_lo = jax.lax.all_to_all(buf_lo, axis, 0, 0, tiled=False)
        r_valid = jax.lax.all_to_all(valid, axis, 0, 0, tiled=False)
        flat_hi, flat_lo = r_hi.reshape(-1), r_lo.reshape(-1)
        flat_valid = r_valid.reshape(-1)
        if op == "insert":
            out = fops.insert_table(table, flat_hi, flat_lo, n_buckets=n_buckets,
                                    valid=flat_valid, stash=stash)
        else:
            out = fops.delete_table(table, flat_hi, flat_lo, n_buckets=n_buckets,
                                    valid=flat_valid, stash=stash)
        if has_stash:
            new_table, new_stash, ok_flat = out
        else:
            new_table, ok_flat = out
            new_stash = stashes[0]          # dummy passthrough
        ok = ok_flat.reshape(n_shards, cap) & r_valid
        back = jax.lax.all_to_all(ok, axis, 0, 0, tiled=False)
        ok_lane = fits & back[dst.clip(0, n_shards - 1), rank]
        deferred = ~fits & lane_valid       # never attempted: resubmit
        return (new_table[None], new_stash[None], ok_lane, deferred,
                overflow[None])

    # Named for the device trace, as ``routed_lookup`` is.
    routed_write.__name__ = routed_write.__qualname__ = f"routed_{op}"
    mapped = _shard_map_unchecked(
        routed_write, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis),) * 5)
    return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())


def _distributed_write(op: str, mesh: Mesh, axis: str,
                       state: ShardedFilterState, hi, lo, *, fp_bits: int,
                       capacity_factor: float, backend: str,
                       evict_rounds: Optional[int], max_disp: int,
                       schedule: bool, donate: bool, valid=None,
                       route: str = "key"):
    n_shards = mesh.shape[axis]
    cap = _capacity(hi.shape[0], n_shards, capacity_factor)
    has_stash = state.stashes is not None
    route_nb = (state.n_buckets if state.n_buckets is not None
                else state.tables.shape[1])
    fn = _routed_write_fn(mesh, axis, op, n_shards, cap, fp_bits, backend,
                          evict_rounds, max_disp, schedule, donate,
                          state.n_buckets, has_stash, route, route_nb)
    stashes = (state.stashes if has_stash else
               jnp.zeros((n_shards, 2, 1), jnp.uint32))  # dummy, threaded
    if valid is None:
        valid = jnp.ones(hi.shape, bool)
    tables, stashes, ok, deferred, overflow = fn(state.tables, stashes,
                                                 hi, lo, valid)
    new_state = state._replace(tables=tables,
                               stashes=stashes if has_stash else None)
    return new_state, ok, deferred, overflow


def distributed_insert(mesh: Mesh, axis: str, state: ShardedFilterState,
                       hi: jax.Array, lo: jax.Array, *, fp_bits: int,
                       capacity_factor: float = 2.0, backend: str = "auto",
                       evict_rounds: Optional[int] = None,
                       max_disp: int = 500, schedule: bool = True,
                       donate: bool = False, valid=None,
                       route: str = "key"):
    """Routed bulk insert across filter shards, entirely on-device.

    ``hi``/``lo``: uint32[n_shards * per_shard] keys, sharded over ``axis``.
    Each key rides the capacity-bounded all_to_all to its owner shard,
    which runs the conflict-aware scheduled insert (optimistic rounds +
    bounded eviction chains + spill to the shard's stash) on its table
    slice inside ``shard_map`` — no host round-trip, no table copy when
    ``donate=True`` (the enclosing jit aliases the table/stash stacks
    in->out; only callers that never reuse the pre-op state qualify,
    exactly the single-node donation contract).

    Returns ``(new_state, ok bool[N], deferred bool[N],
    overflow int32[n_shards])``:

      * ``ok`` — key resident (table or stash) on its owner shard;
      * ``deferred`` — routing overflow: the lane exceeded its owner's
        all_to_all capacity and was NEVER attempted.  Resubmit these
        (``hi[deferred]``) next step; the count is the burst signal the
        EOF/admission policy consumes, exactly like the lookup overflow.
      * ``overflow`` — per-source-shard deferred counts (the device-side
        aggregate of ``deferred``).

    ``ok=False`` with ``deferred=False`` means the shard genuinely failed
    the insert (chain budget exhausted AND stash full) — the rotate/grow
    signal, identical to single-node ``FilterOps.insert``.

    ``evict_rounds`` bounds the kernel arm's eviction rounds (None -> the
    0.85-load default); ``max_disp`` bounds the jnp arm's sequential
    chains — the same two knobs, same semantics, as ``FilterOps``.

    ``valid`` masks lanes out entirely (never routed, never attempted,
    never deferred) — what lets a resubmission pump pad a deferred batch
    to the sharded shape without inserting sentinel keys
    (``serving.scheduler.DeferredWritePump``).

    ``route`` selects the owner function ("key" hashes the full key,
    "pair" hashes the candidate bucket pair + fingerprint — elastic
    states that must re-derive ownership from resident slots).  A state
    must be written and probed under ONE routing mode for its lifetime.
    """
    return _distributed_write("insert", mesh, axis, state, hi, lo,
                              fp_bits=fp_bits,
                              capacity_factor=capacity_factor,
                              backend=backend, evict_rounds=evict_rounds,
                              max_disp=max_disp, schedule=schedule,
                              donate=donate, valid=valid, route=route)


def distributed_delete(mesh: Mesh, axis: str, state: ShardedFilterState,
                       hi: jax.Array, lo: jax.Array, *, fp_bits: int,
                       capacity_factor: float = 2.0, backend: str = "auto",
                       donate: bool = False, valid=None,
                       route: str = "key"):
    """Routed verified delete across filter shards, entirely on-device.

    The write-side mirror of ``distributed_lookup``: each key deletes on
    its owner shard through the fused first-match-slot kernel; lanes that
    miss the table clear the shard's stash entry in the same composed pass
    (table copies first), so keys that parked in a stash during a burst
    are deletable like residents.  Same return contract as
    ``distributed_insert`` — ``ok`` is the per-key verified-delete result,
    ``deferred`` the never-attempted routing overflow to resubmit.

    Callers must pre-verify membership (the OCF keystore does): blind
    deletes corrupt foreign fingerprints on every cuckoo filter, sharded
    or not.
    """
    return _distributed_write("delete", mesh, axis, state, hi, lo,
                              fp_bits=fp_bits,
                              capacity_factor=capacity_factor,
                              backend=backend, evict_rounds=None,
                              max_disp=500, schedule=False, donate=donate,
                              valid=valid, route=route)


def routed_delete_steps(state: ShardedFilterState, hi: np.ndarray,
                        lo: np.ndarray, valid: np.ndarray, n_shards: int, *,
                        fp_bits: int, capacity_factor: float = 2.0,
                        backend: str = "auto", route: str = "key") -> int:
    """Grid steps the shard-local delete of ``distributed_delete`` runs on
    its busiest shard, worked out on the host from the call's lanes.

    Each owner receives ``n_shards`` rows of ``cap`` slots, a source's keys
    for it first in each row, and its kernel-arm delete runs the steps of
    ``kops.delete_block`` lanes that hold one (``live_steps``).  0 when the
    shard-local delete takes the jnp arm, which has no grid.
    """
    if FilterOps(fp_bits=fp_bits, backend=backend).resolve() != "pallas":
        return 0
    if route == "pair":
        nb = (state.n_buckets if state.n_buckets is not None
              else state.tables.shape[1])
        owner = hashing.owner_shard_key_pair_np(hi, lo, nb, fp_bits, n_shards)
    else:
        owner = hashing.owner_shard_np(hi, lo, n_shards)
    rows = np.where(valid, owner, n_shards).reshape(n_shards, -1)
    cap = _capacity(hi.size, n_shards, capacity_factor)
    sent = np.minimum(np.stack([np.bincount(r, minlength=n_shards + 1)
                                for r in rows])[:, :n_shards], cap)
    lanes = n_shards * cap
    buf, bucket_size = state.tables.shape[1:]
    block = kops.delete_block(lanes, table_bytes=buf * bucket_size * 4)
    slot = np.arange(cap)
    return max(int(live_steps(np.pad((slot < sent[:, d, None]).reshape(-1),
                                     (0, -lanes % block)), block).sum())
               for d in range(n_shards))


# ------------------------------------------------- compat shims (host) --
#
# Pre-PR-6 the write path bounced every mutated table through the host:
# gather shard -> single-node op -> scatter back with a whole-stack copy.
# The routed writes above retire that pattern from the hot loop; these
# shims remain for the *control plane* only (rebuild/rotation swaps a
# freshly built table in at generation boundaries, where a copy per
# rotation is irrelevant) and for tests that need to seed one shard.


def local_shard_insert_host(state: ShardedFilterState, shard: int, table
                            ) -> ShardedFilterState:
    """Host-side table swap after a per-shard rebuild (control plane only —
    the hot loop uses ``distributed_insert``)."""
    return state._replace(tables=state.tables.at[shard].set(table))


def local_shard_delete_host(state: ShardedFilterState, shard: int,
                            hi: jax.Array, lo: jax.Array, *, fp_bits: int,
                            backend: str = "auto", n_buckets=None
                            ) -> tuple[ShardedFilterState, jax.Array]:
    """Verified delete on one shard via a host round-trip (compat shim —
    the hot loop uses ``distributed_delete``).

    ``n_buckets`` defaults to the state's ACTIVE bucket count, falling back
    to the buffer row count only for legacy states that never set one —
    the same active-vs-buffer discipline as the single-node path
    (``core/filter.py``: the table lives in a preallocated pow2 buffer, so
    hashing mod ``table.shape[0]`` is wrong whenever the active count is
    smaller; deletes would probe the wrong buckets and silently miss).
    Returns (new_state, deleted bool[N]).
    """
    table = state.tables[shard]
    if n_buckets is None:
        n_buckets = (state.n_buckets if state.n_buckets is not None
                     else table.shape[0])
    st = jfilter.FilterState(table, jnp.zeros((), jnp.int32),
                             jnp.asarray(n_buckets, jnp.int32))
    st, ok = FilterOps(fp_bits=fp_bits, backend=backend).delete(st, hi, lo)
    return state._replace(tables=state.tables.at[shard].set(st.table)), ok


@functools.partial(jax.jit, static_argnames=("fp_bits", "backend"))
def replicated_lookup(tables: jax.Array, hi: jax.Array, lo: jax.Array, *,
                      fp_bits: int, backend: str = "auto") -> jax.Array:
    """Probe every shard (broadcast query — 'is this key anywhere?')."""
    hit = jax.vmap(lambda t: _local_probe(t, hi, lo, fp_bits, backend))(tables)
    return jnp.any(hit, axis=0)
