"""Set-up of a filter sharded by key over a mesh: every shard loaded to
its target on its own chip, from the seed.

Each round, every chip makes its own contiguous share of the next member
keys (``bench.keys``) and routes them to their owner shards with the
program's owner function (``hashing.owner_shard``, ``route="key"``) in one
capacity-bounded ``all_to_all``, as the program's routed ops do.  Each
owner places what it received with ``bench.placement``'s two vectorised
rounds, up to the slots it still misses, and where each key landed goes
back the same way.  So a chip places only the keys it owns, and no chip
makes the whole stream.  A key that is not placed (both buckets full, over
the routing capacity, or its shard at its target) is never a member.

The tables are the program's ``ShardedFilterState`` format: a
``uint32[n_shards, n_buckets, bucket_size]`` stack, one shard per chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import keys as K
from bench import placement


def _rank(owner, valid, n: int):
    """Each valid lane's rank among the valid lanes of its owner, in lane
    order, and ``owner.size`` for an invalid lane: a running count per
    owner (``n`` owners), which compiles far faster than a sort of the
    lanes."""
    mine = (owner[:, None] == jnp.arange(n)[None, :]) & valid[:, None]
    count = jnp.cumsum(mine, axis=0, dtype=jnp.int32)
    rank = jnp.take_along_axis(count, owner[:, None], axis=1)[:, 0] - 1
    return jnp.where(valid, rank, owner.size)


@functools.lru_cache(maxsize=4)
def _round_fn(mesh, axis: str, chunk: int, cap: int, fp_bits: int):
    from repro.core import hashing
    n = mesh.shape[axis]

    def place_round(tables, rk, start, n_offer, need):
        d = jax.lax.axis_index(axis).astype(jnp.uint32)
        lane = jnp.arange(chunk, dtype=jnp.uint32)
        valid = lane < n_offer
        hi, lo = K.keys_hilo_jnp(rk, K.MEMBER, start + d * n_offer + lane)
        owner = hashing.owner_shard(hi, lo, n).astype(jnp.int32)
        rank = _rank(owner, valid, n)
        sent = valid & (rank < cap)
        dst = jnp.where(sent, owner, n)

        def exchange(x):
            buf = jnp.zeros((n, cap), x.dtype).at[dst, rank].set(
                x, mode="drop")
            return jax.lax.all_to_all(buf, axis, 0, 0, tiled=False)

        # What a chip received sits at the head of each source's row; the
        # first ``need`` of them, in source order, are taken.
        r_valid = exchange(sent)
        got = jnp.sum(r_valid, axis=1, dtype=jnp.int32)
        before = jnp.cumsum(got) - got
        take = (r_valid & (before[:, None] + jnp.arange(cap)[None, :]
                           < need[0])).reshape(-1)
        table, fits = placement._place(
            tables[0], exchange(hi).reshape(-1), exchange(lo).reshape(-1),
            take, fp_bits=fp_bits)
        back = jax.lax.all_to_all(fits.reshape(n, cap), axis, 0, 0,
                                  tiled=False)
        placed = sent & back[jnp.clip(owner, 0, n - 1),
                             jnp.clip(rank, 0, cap - 1)]
        return (table[None], jnp.packbits(placed),
                jnp.sum(fits, dtype=jnp.int32)[None])

    mapped = jax.shard_map(place_round, mesh=mesh,
                           in_specs=(P(axis), P(), P(), P(), P(axis)),
                           out_specs=(P(axis), P(axis), P(axis)),
                           check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,))


def load_tables(mesh, axis: str, n_buckets: int, bucket_size: int, *,
                seed: int, load: float, chunk: int, fp_bits: int):
    """Every shard filled to ``load`` -> (tables, member mask
    bool[offered]); ``chunk`` keys per chip and round."""
    n = mesh.shape[axis]
    lanes = NamedSharding(mesh, P(axis))
    tables = jax.jit(lambda: jnp.zeros((n, n_buckets, bucket_size),
                                       jnp.uint32), out_shardings=lanes)()
    rk = jax.device_put(K.round_keys(seed), NamedSharding(mesh, P()))
    target = int(load * n_buckets * bucket_size)
    tol = max(1, int(placement.TOL_SHARE * n_buckets * bucket_size))
    # A chip sends about chunk / n keys to each owner: room for 8 sigma more.
    cap = min(chunk, chunk // n + 8 * math.isqrt(chunk) + 8)
    place = _round_fn(mesh, axis, chunk, cap, fp_bits)
    placed = np.zeros(n, np.int64)
    masks, start = [], 0
    while (target - placed).max() >= tol:
        need = (target - placed).astype(np.int32)
        n_offer = int(min(chunk, need.max()))
        tables, mask, got = place(tables, rk, np.uint32(start),
                                  np.uint32(n_offer),
                                  jax.device_put(need, lanes))
        masks.append((mask, n_offer))
        start += n * n_offer
        placed += np.asarray(got)
    return tables, np.concatenate(
        [np.unpackbits(np.asarray(m)).reshape(n, chunk)[:, :k].reshape(-1)
         .astype(bool) for m, k in masks]) if masks else np.zeros(0, bool)
