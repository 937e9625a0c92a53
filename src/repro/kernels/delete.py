"""Pallas TPU kernel: fused hash + first-match-slot bulk delete.

The device-side analogue of ``core.filter.bulk_delete`` — deletes are what
distinguish a cuckoo filter from a Bloom filter, and until PR 3 they were
the last ``FilterOps`` op stuck on the sequential ``lax.scan`` path.  One
kernel pass hashes each key, probes the home bucket and (for lanes that
missed there) the alternate bucket, and clears exactly one matching slot
per successful lane.

Schedule — same layout strategy as ``probe.py`` / ``insert.py``:
  * the table (the OCF's pow2 buffer) is block-resident in VMEM and aliased
    input→output, so grid steps accumulate clears — TPU grids execute
    sequentially, which makes block b's deletes visible to block b+1;
  * the ACTIVE bucket count is a ``(1, 1)`` SMEM scalar;
  * keys are tiled ``(BLOCK,)``; duplicate keys inside a block are resolved
    with the broadcast-compare rank used by the insert kernel, refined to
    (bucket, fingerprint) pairs: lane i's rank counts earlier lanes
    clearing the same fingerprint from the same bucket, and lane i claims
    the rank-th matching slot.  That reproduces the sequential scan exactly
    for duplicate keys — the k-th duplicate clears the k-th copy, and
    duplicates beyond the resident multiplicity report False.

Parity caveat: the kernel runs all home-bucket attempts before all
alternate-bucket attempts, while the scan interleaves them per key.  For
verified deletes (every requested key resident — what the OCF keystore
guarantees) and for duplicate keys the outcomes are identical; the one
divergence is *unverified* deletes where two DISTINCT keys collide on the
same 16-bit fingerprint with conjugate buckets and fewer resident copies
than requests — there the two orders can credit a different lane.  Blind
deletes corrupt any cuckoo filter anyway, so the control plane never issues
them.

Hash math is imported from ``repro.core.hashing`` — one spec for kernels,
host data plane, and the numpy oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.kernels.rank import rank_among_earlier
from repro.kernels.selector import fp_family, select_fp, sel_pack, sel_unpack

DEFAULT_BLOCK = 1024


def _clear_round(table, target, active, fp):
    """One clear attempt for every active lane in ``target`` buckets.

    Returns (table, cleared).  Rank = #earlier active lanes clearing the
    same fingerprint from the same bucket; a lane succeeds when its rank is
    below the bucket's match count and zeroes the rank-th matching slot, so
    duplicate lanes of one bucket never race for a slot.
    """
    buf, _bucket_size = table.shape
    rank = rank_among_earlier(target, active, fp=fp)
    tgt_c = jnp.clip(target, 0, buf - 1)
    row = table[tgt_c]                                    # [n, bucket_size]
    match = row == fp[:, None]
    hits = active & (rank < jnp.sum(match, axis=1).astype(jnp.int32))
    match_pos = jnp.cumsum(match.astype(jnp.int32), axis=1) - 1
    is_dest = match & (match_pos == rank[:, None])
    slot = jnp.argmax(is_dest, axis=1)
    upd_i = jnp.where(hits, target, buf)                  # OOB -> dropped
    table = table.at[upd_i, slot].set(jnp.uint32(0), mode="drop")
    return table, hits


def _delete_body(table, hi, lo, valid, n_buckets, *, fp_bits: int):
    """Hash + home/alternate clear rounds on loaded values -> (table, ok)."""
    fp = hashing.fingerprint(hi, lo, fp_bits)
    i1 = hashing.index_hash_dyn(hi, lo, n_buckets).astype(jnp.int32)
    i2 = hashing.alt_index_dyn(i1, fp, n_buckets).astype(jnp.int32)
    table, ok1 = _clear_round(table, i1, valid, fp)
    table, ok2 = _clear_round(table, i2, valid & ~ok1, fp)
    return table, ok1 | ok2


def live_steps(valid, block: int):
    """Which grid steps of ``block`` lanes hold a valid lane -> bool[steps].

    ``valid`` is a NumPy or JAX bool array whose length is a block multiple
    (the kernel wrappers pad to one).  The emulated grid runs exactly these
    steps: a step of padding lanes clears nothing.
    """
    return valid.reshape(-1, block).any(axis=1)


def _emulated_grid(body, carry, hi, lo, valid, block: int):
    """The kernel's sequential grid as one compiled loop -> (carry, ok[N]).

    ``body(carry, hi, lo, valid) -> (carry, ok)`` runs one block; the carry
    (the table, or the adaptive planes) passes from block to block, so
    block b's clears are visible to block b+1, as in the pallas_call.  Only
    the steps ``live_steps`` names run, in grid order: a step whose lanes
    are all ``valid=False`` leaves the table as it is and answers False,
    so skipping it changes nothing.
    """
    g = hi.shape[0] // block
    hi, lo, valid = (x.reshape(g, block) for x in (hi, lo, valid))
    live = live_steps(valid, block)
    order = jnp.nonzero(live, size=g, fill_value=0)[0]

    def step(i, state):
        carry, ok = state
        s = order[i]
        carry, ok_s = body(carry, hi[s], lo[s], valid[s])
        return carry, ok.at[s].set(ok_s)

    carry, ok = jax.lax.fori_loop(0, jnp.sum(live, dtype=jnp.int32), step,
                                  (carry, jnp.zeros((g, block), bool)))
    return carry, ok.reshape(-1)


def _delete_kernel(n_ref, table_in_ref, hi_ref, lo_ref, valid_ref, table_ref,
                   ok_ref, *, fp_bits: int):
    del table_in_ref  # aliased to table_ref (the output) — read/write there
    table, ok = _delete_body(table_ref[...], hi_ref[...], lo_ref[...],
                             valid_ref[...], n_ref[0, 0], fp_bits=fp_bits)
    table_ref[...] = table
    ok_ref[...] = ok


def _delete_bulk_impl(table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                      fp_bits: int, n_buckets=None, valid=None,
                      block: int = DEFAULT_BLOCK, interpret: bool = True,
                      emulate: bool = False) -> tuple[jax.Array, jax.Array]:
    n = hi.shape[0]
    block = min(block, n)
    assert n % block == 0, f"{n=} not a multiple of {block=}"
    buffer_buckets, bucket_size = table.shape
    if n_buckets is None:
        n_buckets = buffer_buckets
    if valid is None:
        valid = jnp.ones((n,), bool)
    hi = hi.astype(jnp.uint32)
    lo = lo.astype(jnp.uint32)
    if emulate:
        if n == block:
            return _delete_body(table, hi, lo, valid, n_buckets,
                                fp_bits=fp_bits)
        return _emulated_grid(
            lambda tbl, *x: _delete_body(tbl, *x, n_buckets,
                                         fp_bits=fp_bits),
            table, hi, lo, valid, block)
    n_arr = jnp.asarray(n_buckets, jnp.int32).reshape(1, 1)
    grid = (n // block,)
    smem_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)
    key_spec = pl.BlockSpec((block,), lambda i: (i,))
    table_spec = pl.BlockSpec((buffer_buckets, bucket_size), lambda i: (0, 0))
    new_table, ok = pl.pallas_call(
        functools.partial(_delete_kernel, fp_bits=fp_bits),
        grid=grid,
        in_specs=[smem_spec, table_spec, key_spec, key_spec, key_spec],
        out_specs=[table_spec, pl.BlockSpec((block,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct((n,), jnp.bool_)],
        input_output_aliases={1: 0},   # table updates in place across steps
        interpret=interpret,
    )(n_arr, table, hi, lo, valid)
    return new_table, ok


# ------------------------------------------- selector-aware (adaptive) -----


def _clear_round_adaptive(planes, target, active, fam, fp0):
    """Adaptive clear round: a slot matches when it stores the lane's
    fingerprint under the SLOT's selector; clearing zeroes all four planes.

    Duplicate rank stays keyed on (bucket, selector-0 fingerprint) — lanes
    deleting the same key share fp0 whatever the resident slots' selectors
    are, so the k-th duplicate still clears the k-th matching copy.
    """
    table, sel_tbl, khi_t, klo_t = planes
    buf, _bucket_size = table.shape
    rank = rank_among_earlier(target, active, fp=fp0)
    tgt_c = jnp.clip(target, 0, buf - 1)
    row = table[tgt_c]                                    # [n, bucket_size]
    match = row == select_fp(fam, sel_tbl[tgt_c])
    hits = active & (rank < jnp.sum(match, axis=1).astype(jnp.int32))
    match_pos = jnp.cumsum(match.astype(jnp.int32), axis=1) - 1
    is_dest = match & (match_pos == rank[:, None])
    slot = jnp.argmax(is_dest, axis=1)
    upd_i = jnp.where(hits, target, buf)                  # OOB -> dropped
    table = table.at[upd_i, slot].set(jnp.uint32(0), mode="drop")
    sel_tbl = sel_tbl.at[upd_i, slot].set(jnp.uint32(0), mode="drop")
    khi_t = khi_t.at[upd_i, slot].set(jnp.uint32(0), mode="drop")
    klo_t = klo_t.at[upd_i, slot].set(jnp.uint32(0), mode="drop")
    return (table, sel_tbl, khi_t, klo_t), hits


def _delete_adaptive_body(table, sels, khi_t, klo_t, hi, lo, valid, n_buckets,
                          *, fp_bits: int):
    """Hash family + home/alternate adaptive clear rounds.

    With an all-zero selector plane this is bit-for-bit ``_delete_body`` on
    the fingerprint plane (selector-0 expected fps == static fps).
    """
    bucket_size = table.shape[-1]
    sel_tbl = sel_unpack(sels, bucket_size)
    fam = fp_family(hi, lo, fp_bits)
    fp0 = fam[0]
    i1 = hashing.index_hash_dyn(hi, lo, n_buckets).astype(jnp.int32)
    i2 = hashing.alt_index_dyn(i1, fp0, n_buckets).astype(jnp.int32)
    planes = (table, sel_tbl, khi_t, klo_t)
    planes, ok1 = _clear_round_adaptive(planes, i1, valid, fam, fp0)
    planes, ok2 = _clear_round_adaptive(planes, i2, valid & ~ok1, fam, fp0)
    table, sel_tbl, khi_t, klo_t = planes
    return table, sel_pack(sel_tbl), khi_t, klo_t, ok1 | ok2


def _delete_adaptive_kernel(n_ref, table_in, sels_in, khi_in, klo_in, hi_ref,
                            lo_ref, valid_ref, table_ref, sels_ref, khi_ref,
                            klo_ref, ok_ref, *, fp_bits: int):
    del table_in, sels_in, khi_in, klo_in      # aliased to the outputs
    table, sels, khi_t, klo_t, ok = _delete_adaptive_body(
        table_ref[...], sels_ref[...], khi_ref[...], klo_ref[...],
        hi_ref[...], lo_ref[...], valid_ref[...], n_ref[0, 0],
        fp_bits=fp_bits)
    table_ref[...] = table
    sels_ref[...] = sels
    khi_ref[...] = khi_t
    klo_ref[...] = klo_t
    ok_ref[...] = ok


def _delete_adaptive_impl(table, sels, khi_t, klo_t, hi, lo, *, fp_bits: int,
                          n_buckets=None, valid=None,
                          block: int = DEFAULT_BLOCK, interpret: bool = True,
                          emulate: bool = False):
    n = hi.shape[0]
    block = min(block, n)
    assert n % block == 0, f"{n=} not a multiple of {block=}"
    buffer_buckets, bucket_size = table.shape
    if n_buckets is None:
        n_buckets = buffer_buckets
    if valid is None:
        valid = jnp.ones((n,), bool)
    hi = hi.astype(jnp.uint32)
    lo = lo.astype(jnp.uint32)
    if emulate:
        if n == block:
            return _delete_adaptive_body(table, sels, khi_t, klo_t, hi, lo,
                                         valid, n_buckets, fp_bits=fp_bits)

        def step(planes, *x):
            *planes, ok = _delete_adaptive_body(*planes, *x, n_buckets,
                                                fp_bits=fp_bits)
            return tuple(planes), ok

        (table, sels, khi_t, klo_t), ok = _emulated_grid(
            step, (table, sels, khi_t, klo_t), hi, lo, valid, block)
        return table, sels, khi_t, klo_t, ok
    n_arr = jnp.asarray(n_buckets, jnp.int32).reshape(1, 1)
    grid = (n // block,)
    smem_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)
    key_spec = pl.BlockSpec((block,), lambda i: (i,))
    table_spec = pl.BlockSpec((buffer_buckets, bucket_size), lambda i: (0, 0))
    sel_spec = pl.BlockSpec((buffer_buckets, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_delete_adaptive_kernel, fp_bits=fp_bits),
        grid=grid,
        in_specs=[smem_spec, table_spec, sel_spec, table_spec, table_spec,
                  key_spec, key_spec, key_spec],
        out_specs=[table_spec, sel_spec, table_spec, table_spec,
                   pl.BlockSpec((block,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct(table.shape, jnp.uint32),
                   jax.ShapeDtypeStruct((buffer_buckets, 1), jnp.uint32),
                   jax.ShapeDtypeStruct(table.shape, jnp.uint32),
                   jax.ShapeDtypeStruct(table.shape, jnp.uint32),
                   jax.ShapeDtypeStruct((n,), jnp.bool_)],
        input_output_aliases={1: 0, 2: 1, 3: 2, 4: 3},
        interpret=interpret,
    )(n_arr, table, sels, khi_t, klo_t, hi, lo, valid)
    return out


_DELETE_STATICS = ("fp_bits", "block", "interpret", "emulate")
_delete_bulk_jit = jax.jit(_delete_bulk_impl, static_argnames=_DELETE_STATICS)
_delete_bulk_donated = jax.jit(_delete_bulk_impl,
                               static_argnames=_DELETE_STATICS,
                               donate_argnames=("table",))


def delete_bulk(table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                fp_bits: int, n_buckets=None, valid=None,
                block: int = DEFAULT_BLOCK, interpret: bool = True,
                emulate: bool = False, donate: bool = False
                ) -> tuple[jax.Array, jax.Array]:
    """Fused bulk delete -> (new_table, deleted bool[N]).

    N must be a block multiple (ops.py pads).  ``n_buckets`` is the ACTIVE
    bucket count (may be < ``table.shape[0]`` for the OCF's pow2 buffer).
    Lanes with ``valid=False`` never touch the table.  Callers are expected
    to have verified membership against the keystore (the OCF control plane
    does) — like every cuckoo delete, clearing a fingerprint that was never
    inserted corrupts another key's slot.

    ``emulate`` runs the identical grid as a compiled XLA loop over the
    steps that hold a valid lane (the off-TPU fast path); ``donate`` hands
    the table buffer to the call so the cleared table is written in place
    (callers must own the buffer — the OCF control plane does).  Deletes
    are never wave-scheduled: duplicate keys must clear the k-th resident
    copy in lane order.
    """
    fn = _delete_bulk_donated if donate else _delete_bulk_jit
    return fn(table, hi, lo, fp_bits=fp_bits, n_buckets=n_buckets,
              valid=valid, block=block, interpret=interpret, emulate=emulate)


_delete_adaptive_jit = jax.jit(_delete_adaptive_impl,
                               static_argnames=_DELETE_STATICS)
_delete_adaptive_donated = jax.jit(
    _delete_adaptive_impl, static_argnames=_DELETE_STATICS,
    donate_argnames=("table", "sels", "khi_t", "klo_t"))


def delete_bulk_adaptive(table, sels, khi_t, klo_t, hi, lo, *, fp_bits: int,
                         n_buckets=None, valid=None,
                         block: int = DEFAULT_BLOCK, interpret: bool = True,
                         emulate: bool = False, donate: bool = False):
    """Selector-aware bulk delete -> (table, sels, khi, klo, deleted).

    Same contract as ``delete_bulk``; a slot matches under ITS selector
    (so an adapted resident is still deletable by its key), and clearing
    zeroes the selector and mirror-key planes along with the fingerprint.
    Overflow-stash entries hold selector-0 fingerprints — callers compose
    ``kernels.stash.stash_delete_ref`` for lanes that miss the table,
    exactly like the static path.
    """
    fn = _delete_adaptive_donated if donate else _delete_adaptive_jit
    return fn(table, sels, khi_t, klo_t, hi, lo, fp_bits=fp_bits,
              n_buckets=n_buckets, valid=valid, block=block,
              interpret=interpret, emulate=emulate)
