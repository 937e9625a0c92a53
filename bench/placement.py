"""Set-up: a table loaded to its target on the device, from the seed.

A deployment's table at load 0.85 is what its insert stream leaves.  Greedy
two-choice placement with no evictions leaves the same bucket-fill
histogram, and a few hundred times faster than the program's insert, which
runs eviction chains: each chunk of the member stream is placed in two
vectorised rounds (home bucket, then alternate), lanes ranked within a
bucket by lane order.  Keys that find both buckets full are not placed and
are never members.

The placement writes the program's table format directly: a
``uint32[n_buckets, bucket_size]`` table whose slot holds the key's
fingerprint (0 is empty), in one of its two candidate buckets, with the
program's hash functions (``repro.core.hashing``).  The set-up therefore
depends on that format, and on nothing else of the program.

Chunks offer contiguous ranges of the member stream: at most ``chunk``
keys, and never more than the slots still missing, so the load stops at
the target from below, within ``tol`` slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import keys as K

TOL_SHARE = 0.0005     # stop once fewer than this share of slots is missing


def _round(table, target, fp, active):
    """Place ``active`` lanes into their ``target`` buckets -> (table, fits)."""
    nb, _bs = table.shape
    n = target.shape[0]
    tgt = jnp.where(active, target, nb).astype(jnp.int32)
    lane = jnp.arange(n, dtype=jnp.int32)
    s_tgt, order = jax.lax.sort((tgt, lane), num_keys=1, is_stable=True)
    first = jnp.concatenate([jnp.ones((1,), bool), s_tgt[1:] != s_tgt[:-1]])
    run_start = jax.lax.cummax(jnp.where(first, lane, 0))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(lane - run_start)
    row = table[jnp.clip(target, 0, nb - 1)]                 # [n, bs]
    empty = row == 0
    free = jnp.sum(empty, axis=1, dtype=jnp.int32)
    fits = active & (rank < free)
    empty_pos = jnp.cumsum(empty.astype(jnp.int32), axis=1) - 1
    slot = jnp.argmax(empty & (empty_pos == rank[:, None]), axis=1)
    upd = jnp.where(fits, target, nb)                          # OOB: dropped
    return table.at[upd, slot].set(fp, mode="drop"), fits


def _place(table, hi, lo, valid, *, fp_bits):
    from repro.core import hashing
    nb = table.shape[0]
    fp = hashing.fingerprint(hi, lo, fp_bits)
    i1 = hashing.index_hash(hi, lo, nb).astype(jnp.int32)
    i2 = hashing.alt_index(i1, fp, nb).astype(jnp.int32)
    table, ok1 = _round(table, i1, fp, valid)
    table, ok2 = _round(table, i2, fp, valid & ~ok1)
    return table, ok1 | ok2


@functools.partial(jax.jit, static_argnames=("chunk", "fp_bits"),
                   donate_argnums=(0,))
def place_chunk(table, rk, start, n_offer, *, chunk, fp_bits):
    """Offer member keys ``start .. start+n_offer`` -> (table, placed[chunk],
    count placed)."""
    lane = jnp.arange(chunk, dtype=jnp.uint32)
    hi, lo = K.keys_hilo_jnp(rk, K.MEMBER, start.astype(jnp.uint32) + lane)
    valid = lane < n_offer.astype(jnp.uint32)
    table, placed = _place(table, hi, lo, valid, fp_bits=fp_bits)
    return table, placed, jnp.sum(placed, dtype=jnp.int32)


def load_table(n_buckets: int, bucket_size: int, *, seed: int, load: float,
               chunk: int, fp_bits: int, device=None):
    """A table filled to ``load`` -> (table, member mask bool[offered])."""
    table = jnp.zeros((n_buckets, bucket_size), jnp.uint32, device=device)
    rk = jax.device_put(K.round_keys(seed), device)
    target = int(load * n_buckets * bucket_size)
    tol = max(1, int(TOL_SHARE * n_buckets * bucket_size))
    masks, start, placed = [], 0, 0
    while target - placed >= tol:
        n_offer = min(chunk, target - placed)
        table, mask, got = place_chunk(table, rk, np.uint32(start),
                                       np.uint32(n_offer), chunk=chunk,
                                       fp_bits=fp_bits)
        masks.append((jnp.packbits(mask), n_offer))
        start += n_offer
        placed += int(got)
    return table, _unpack(masks)


def _unpack(masks) -> np.ndarray:
    return np.concatenate([np.unpackbits(np.asarray(p))[:n].astype(bool)
                           for p, n in masks]) if masks else np.zeros(0, bool)
