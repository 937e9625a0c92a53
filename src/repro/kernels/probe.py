"""Pallas TPU kernel: fused hash + bucket-probe bulk lookup (the serving
hot path of the OCF).

Layout strategy (TPU adaptation of the paper's pointer-chasing lookup):
  * the bucket table ``uint32[buffer_buckets, bucket_size]`` is block-resident
    in VMEM — the BlockSpec index_map pins the whole table for every program
    (capacity ≤ ~2M slots ⇒ ≤ 8 MB, inside the ~16 MB VMEM budget; larger
    filters shard first — see core.distributed);
  * the ACTIVE bucket count rides along as a ``(1, 1)`` SMEM scalar, so the
    kernel probes the same dynamic-capacity state the OCF control plane
    resizes — one compiled kernel per buffer size, never per active size;
  * keys are tiled ``(BLOCK,)`` over a 1-D grid, hashing is fused so a key is
    read once from HBM and never revisited;
  * both candidate buckets are gathered from VMEM and compared per lane —
    2·bucket_size uint32 compares per key on the VPU, no MXU involvement;
  * with an overflow stash attached (``kernels/stash.py``), the same pass
    also broadcast-compares each lane against the stash — a stashed
    fingerprint (spilled by the insert kernel when an eviction chain
    exhausted its budget) answers True exactly like a resident one, so the
    stash is invisible to every lookup consumer.

The hash math is imported from ``repro.core.hashing`` — one spec shared by
the host data plane, the numpy oracle, and every kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hashing
from repro.kernels.selector import fp_family, select_fp, sel_unpack
from repro.kernels.stash import stash_match
from repro.kernels.telemetry import probe_depth_counts

DEFAULT_BLOCK = 1024


def _probe_body(table_ref, stash, hi, lo, n_buckets, *, fp_bits: int,
                array_table: bool = False, want_stats: bool = False):
    fp = hashing.fingerprint(hi, lo, fp_bits)
    i1 = hashing.index_hash_dyn(hi, lo, n_buckets)
    i2 = hashing.alt_index_dyn(i1, fp, n_buckets)
    if array_table:
        # XLA-emulation arm (table is a plain array): gather with the
        # native uint32 indices (an int32 cast would add a negative-wrap
        # select) and promise bounds — i1/i2 are mod-n_buckets <= buffer
        # rows by construction, so the clamp path XLA emits for a plain
        # table[i1] is dead weight (together ~10% of the lookup).  An
        # explicit flag, not isinstance: interpret-mode ref tracers pass
        # isinstance(x, jax.Array) but reject .at[].get kwargs.
        b1 = table_ref.at[i1].get(mode="promise_in_bounds")
        b2 = table_ref.at[i2].get(mode="promise_in_bounds")
    else:
        # Pallas ref gather: Mosaic wants int32 indices.
        b1 = table_ref[i1.astype(jnp.int32), :]
        b2 = table_ref[i2.astype(jnp.int32), :]
    h1 = jnp.any(b1 == fp[:, None], axis=-1)
    h2 = jnp.any(b2 == fp[:, None], axis=-1)
    hit = h1 | h2
    hs = None
    if stash is not None:
        hs = stash_match(stash, fp, i1, i2)
        hit = hit | hs
    if want_stats:
        # Per-bucket hit components for the probe-depth telemetry plane.
        if hs is None:
            hs = jnp.zeros_like(hit)
        return hit, (h1, h2, hs)
    return hit


def _probe_kernel(n_ref, table_ref, hi_ref, lo_ref, hit_ref, *, fp_bits: int):
    hit_ref[...] = _probe_body(table_ref, None, hi_ref[...], lo_ref[...],
                               n_ref[0, 0], fp_bits=fp_bits)


def _probe_stash_kernel(n_ref, table_ref, stash_ref, hi_ref, lo_ref, hit_ref,
                        *, fp_bits: int):
    hit_ref[...] = _probe_body(table_ref, stash_ref[...], hi_ref[...],
                               lo_ref[...], n_ref[0, 0], fp_bits=fp_bits)


@functools.partial(jax.jit, static_argnames=("fp_bits", "block", "interpret",
                                             "emulate"))
def probe(table: jax.Array, hi: jax.Array, lo: jax.Array, *, fp_bits: int,
          n_buckets=None, stash=None, block: int = DEFAULT_BLOCK,
          interpret: bool = True, emulate: bool = False) -> jax.Array:
    """Bulk membership test -> bool[N].  N must be a block multiple.

    ``n_buckets``: ACTIVE bucket count (int or traced scalar); defaults to
    the full table, i.e. buffer == active.  May be less than
    ``table.shape[0]`` when the table is the OCF's preallocated pow2 buffer.
    ``stash``: optional overflow stash (``kernels.stash``) checked in the
    same fused pass.  ``emulate``: run the identical kernel body as one
    compiled XLA pass instead of ``pallas_call`` — the off-TPU fast path
    (probes don't mutate, so no grid carry is needed: the whole batch is
    one fused body evaluation; bit-for-bit the kernel's answers).
    """
    n = hi.shape[0]
    block = min(block, n)
    assert n % block == 0, f"{n=} not a multiple of {block=}"
    buffer_buckets, bucket_size = table.shape
    if n_buckets is None:
        n_buckets = buffer_buckets
    if emulate:
        return _probe_body(table, stash, hi.astype(jnp.uint32),
                           lo.astype(jnp.uint32), n_buckets, fp_bits=fp_bits,
                           array_table=True)
    n_arr = jnp.asarray(n_buckets, jnp.int32).reshape(1, 1)
    grid = (n // block,)
    smem_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)
    key_spec = pl.BlockSpec((block,), lambda i: (i,))
    table_spec = pl.BlockSpec((buffer_buckets, bucket_size), lambda i: (0, 0))
    out_spec = pl.BlockSpec((block,), lambda i: (i,))
    out_shape = jax.ShapeDtypeStruct((n,), jnp.bool_)
    if stash is None:
        return pl.pallas_call(
            functools.partial(_probe_kernel, fp_bits=fp_bits),
            grid=grid,
            in_specs=[smem_spec, table_spec, key_spec, key_spec],
            out_specs=out_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(n_arr, table, hi.astype(jnp.uint32), lo.astype(jnp.uint32))
    stash_spec = pl.BlockSpec(stash.shape, lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_probe_stash_kernel, fp_bits=fp_bits),
        grid=grid,
        in_specs=[smem_spec, table_spec, stash_spec, key_spec, key_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(n_arr, table, stash, hi.astype(jnp.uint32), lo.astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("fp_bits", "telemetry"))
def probe_emulated(table: jax.Array, hi: jax.Array, lo: jax.Array,
                   n_buckets, stash, *, fp_bits: int,
                   telemetry: bool = False):
    """The emulated probe body behind a minimal positional-arg jit.

    Same function as ``probe(..., emulate=True)``; exists because the hot
    serving lookup is dispatch-bound enough on CPU that the keyword-arg
    jit entry with five statics costs a measurable slice of the call
    (``ops.probe_dispatch`` uses this one).  ``telemetry=True`` returns
    ``(hit, probe_depth uint32[4])``: lanes counted by shallowest hit
    location — first bucket, second bucket, stash, miss
    (``kernels.telemetry.probe_depth_counts``).
    """
    out = _probe_body(table, stash, hi, lo, n_buckets, fp_bits=fp_bits,
                      array_table=True, want_stats=telemetry)
    return _with_depth(out) if telemetry else out


def _with_depth(out):
    """(hit, (h1, h2, hs)) from a body run with ``want_stats`` -> (hit,
    probe-depth counts)."""
    hit, (h1, h2, hs) = out
    return hit, probe_depth_counts(h1, h2, hs, jnp.ones_like(hit))


# --------------------------------------------- selector-aware probe ---------


def _probe_adaptive_body(table_ref, sel_ref, stash, hi, lo, n_buckets, *,
                         fp_bits: int, array_table: bool = False,
                         want_stats: bool = False):
    """Adaptive lookup: compare each slot against the fingerprint the slot's
    selector chose (``kernels/selector.py``).

    Bucket geometry (i1, i2) always comes from the selector-0 fingerprint —
    adaptation rewrites what a slot *stores*, never where the entry *lives*
    — so the candidate pair of a key is stable across repairs.  The stash
    holds selector-0 fingerprints (spills reset adaptation), so the stash
    compare is unchanged.  With an all-zero selector plane this body is
    bit-for-bit ``_probe_body``.
    """
    fam = fp_family(hi, lo, fp_bits)
    fp0 = fam[0]
    i1 = hashing.index_hash_dyn(hi, lo, n_buckets)
    i2 = hashing.alt_index_dyn(i1, fp0, n_buckets)
    bucket_size = table_ref.shape[-1]
    if array_table:
        b1 = table_ref.at[i1].get(mode="promise_in_bounds")
        b2 = table_ref.at[i2].get(mode="promise_in_bounds")
        s1 = sel_ref.at[i1].get(mode="promise_in_bounds")
        s2 = sel_ref.at[i2].get(mode="promise_in_bounds")
    else:
        b1 = table_ref[i1.astype(jnp.int32), :]
        b2 = table_ref[i2.astype(jnp.int32), :]
        s1 = sel_ref[i1.astype(jnp.int32), :]
        s2 = sel_ref[i2.astype(jnp.int32), :]
    e1 = select_fp(fam, sel_unpack(s1, bucket_size))
    e2 = select_fp(fam, sel_unpack(s2, bucket_size))
    h1 = jnp.any(b1 == e1, axis=-1)
    h2 = jnp.any(b2 == e2, axis=-1)
    hit = h1 | h2
    hs = None
    if stash is not None:
        hs = stash_match(stash, fp0, i1, i2)
        hit = hit | hs
    if want_stats:
        if hs is None:
            hs = jnp.zeros_like(hit)
        return hit, (h1, h2, hs)
    return hit


def _probe_adaptive_kernel(n_ref, table_ref, sel_ref, hi_ref, lo_ref, hit_ref,
                           *, fp_bits: int):
    hit_ref[...] = _probe_adaptive_body(table_ref, sel_ref, None, hi_ref[...],
                                        lo_ref[...], n_ref[0, 0],
                                        fp_bits=fp_bits)


def _probe_adaptive_stash_kernel(n_ref, table_ref, sel_ref, stash_ref, hi_ref,
                                 lo_ref, hit_ref, *, fp_bits: int):
    hit_ref[...] = _probe_adaptive_body(table_ref, sel_ref, stash_ref[...],
                                        hi_ref[...], lo_ref[...], n_ref[0, 0],
                                        fp_bits=fp_bits)


@functools.partial(jax.jit, static_argnames=("fp_bits", "block", "interpret",
                                             "emulate"))
def probe_adaptive(table: jax.Array, sels: jax.Array, hi: jax.Array,
                   lo: jax.Array, *, fp_bits: int, n_buckets=None, stash=None,
                   block: int = DEFAULT_BLOCK, interpret: bool = True,
                   emulate: bool = False) -> jax.Array:
    """Selector-aware bulk membership test -> bool[N].

    Same contract as ``probe`` plus ``sels``: the packed per-slot selector
    plane ``uint32[buffer_buckets, 1]`` riding block-resident beside the
    table (2 bits/slot; +1/16th of a table of VMEM at bucket_size 4).
    """
    n = hi.shape[0]
    block = min(block, n)
    assert n % block == 0, f"{n=} not a multiple of {block=}"
    buffer_buckets, bucket_size = table.shape
    if n_buckets is None:
        n_buckets = buffer_buckets
    if emulate:
        return _probe_adaptive_body(table, sels, stash, hi.astype(jnp.uint32),
                                    lo.astype(jnp.uint32), n_buckets,
                                    fp_bits=fp_bits, array_table=True)
    n_arr = jnp.asarray(n_buckets, jnp.int32).reshape(1, 1)
    grid = (n // block,)
    smem_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)
    key_spec = pl.BlockSpec((block,), lambda i: (i,))
    table_spec = pl.BlockSpec((buffer_buckets, bucket_size), lambda i: (0, 0))
    sel_spec = pl.BlockSpec((buffer_buckets, 1), lambda i: (0, 0))
    out_spec = pl.BlockSpec((block,), lambda i: (i,))
    out_shape = jax.ShapeDtypeStruct((n,), jnp.bool_)
    if stash is None:
        return pl.pallas_call(
            functools.partial(_probe_adaptive_kernel, fp_bits=fp_bits),
            grid=grid,
            in_specs=[smem_spec, table_spec, sel_spec, key_spec, key_spec],
            out_specs=out_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(n_arr, table, sels, hi.astype(jnp.uint32), lo.astype(jnp.uint32))
    stash_spec = pl.BlockSpec(stash.shape, lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_probe_adaptive_stash_kernel, fp_bits=fp_bits),
        grid=grid,
        in_specs=[smem_spec, table_spec, sel_spec, stash_spec, key_spec,
                  key_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(n_arr, table, sels, stash, hi.astype(jnp.uint32),
      lo.astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("fp_bits", "telemetry"))
def probe_adaptive_emulated(table: jax.Array, sels: jax.Array, hi: jax.Array,
                            lo: jax.Array, n_buckets, stash, *,
                            fp_bits: int, telemetry: bool = False):
    """Positional-arg fast path for the emulated adaptive probe (the
    adaptive serving lookup's analogue of ``probe_emulated``, telemetry
    included)."""
    out = _probe_adaptive_body(table, sels, stash, hi, lo, n_buckets,
                               fp_bits=fp_bits, array_table=True,
                               want_stats=telemetry)
    return _with_depth(out) if telemetry else out


# ----------------------------------------------- multi-generation probe ----


def _probe_multi_kernel(n_ref, table_ref, hi_ref, lo_ref, hit_ref, *,
                        fp_bits: int):
    """Grid (blocks, K): OR one generation's hits into the block's output.

    The output block is revisited across the K axis (its index_map ignores
    k); TPU grids execute sequentially, so initializing at k == 0 and
    accumulating afterwards is the standard revisit-accumulate pattern.
    """
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        hit_ref[...] = jnp.zeros(hit_ref.shape, jnp.bool_)

    hit_ref[...] |= _probe_body(table_ref[0], None, hi_ref[...], lo_ref[...],
                                n_ref[0, 0], fp_bits=fp_bits)


def _probe_multi_stash_kernel(n_ref, table_ref, stash_ref, hi_ref, lo_ref,
                              hit_ref, *, fp_bits: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        hit_ref[...] = jnp.zeros(hit_ref.shape, jnp.bool_)

    hit_ref[...] |= _probe_body(table_ref[0], stash_ref[0], hi_ref[...],
                                lo_ref[...], n_ref[0, 0], fp_bits=fp_bits)


def _emulated_probe_multi(tables, stashes, hi, lo, n_buckets, *,
                          fp_bits: int):
    """Fused fan-out, XLA-compiled: hash ONCE, gather/compare per generation.

    This is where the fused probe beats the per-generation loop even off
    TPU: the loop hashes every key 2·K times (once in each generation's
    table probe, once in each stash match); here fp/i1/i2 are computed a
    single time and only the table gathers and stash compares fan out.
    """
    fp = hashing.fingerprint(hi, lo, fp_bits)
    i1 = hashing.index_hash_dyn(hi, lo, n_buckets)
    i2 = hashing.alt_index_dyn(i1, fp, n_buckets)

    def one_table(table):
        b1 = table.at[i1].get(mode="promise_in_bounds")
        b2 = table.at[i2].get(mode="promise_in_bounds")
        return (jnp.any(b1 == fp[:, None], axis=-1)
                | jnp.any(b2 == fp[:, None], axis=-1))

    hit = jnp.any(jax.vmap(one_table)(tables), axis=0)
    if stashes is not None:
        hit = hit | jnp.any(
            jax.vmap(lambda s: stash_match(s, fp, i1, i2))(stashes), axis=0)
    return hit


@functools.partial(jax.jit, static_argnames=("fp_bits", "block", "interpret",
                                             "emulate"))
def probe_multi(tables: jax.Array, hi: jax.Array, lo: jax.Array, *,
                fp_bits: int, n_buckets=None, stashes=None,
                block: int = DEFAULT_BLOCK, interpret: bool = True,
                emulate: bool = False) -> jax.Array:
    """Fused multi-generation membership -> bool[N]: one kernel whose grid
    spans every live generation of the preallocated pool.

    ``tables``: uint32[K, buffer_buckets, bucket_size] — the K live
    generations' tables stacked (same shape by construction: they all come
    from the generation ring's one buffer pool).  ``stashes``: optional
    uint32[K, 2, S] stack of their overflow stashes, checked in the same
    pass.  ``n_buckets`` is the generations' shared ACTIVE bucket count.
    Replaces the per-generation probe loop (K kernel launches, 2·K hash
    evaluations per key) with one launch and one hash evaluation.
    """
    n = hi.shape[0]
    block = min(block, n)
    assert n % block == 0, f"{n=} not a multiple of {block=}"
    k, buffer_buckets, bucket_size = tables.shape
    if n_buckets is None:
        n_buckets = buffer_buckets
    if emulate:
        return _emulated_probe_multi(tables, stashes, hi.astype(jnp.uint32),
                                     lo.astype(jnp.uint32), n_buckets,
                                     fp_bits=fp_bits)
    n_arr = jnp.asarray(n_buckets, jnp.int32).reshape(1, 1)
    grid = (n // block, k)
    smem_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                             memory_space=pltpu.SMEM)
    key_spec = pl.BlockSpec((block,), lambda i, j: (i,))
    table_spec = pl.BlockSpec((1, buffer_buckets, bucket_size),
                              lambda i, j: (j, 0, 0))
    out_spec = pl.BlockSpec((block,), lambda i, j: (i,))
    out_shape = jax.ShapeDtypeStruct((n,), jnp.bool_)
    if stashes is None:
        return pl.pallas_call(
            functools.partial(_probe_multi_kernel, fp_bits=fp_bits),
            grid=grid,
            in_specs=[smem_spec, table_spec, key_spec, key_spec],
            out_specs=out_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(n_arr, tables, hi.astype(jnp.uint32), lo.astype(jnp.uint32))
    stash_spec = pl.BlockSpec((1,) + stashes.shape[1:], lambda i, j: (j, 0, 0))
    return pl.pallas_call(
        functools.partial(_probe_multi_stash_kernel, fp_bits=fp_bits),
        grid=grid,
        in_specs=[smem_spec, table_spec, stash_spec, key_spec, key_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(n_arr, tables, stashes, hi.astype(jnp.uint32), lo.astype(jnp.uint32))
