"""Jit'd public wrappers around the Pallas kernels.

Form: ``LOWERING`` below is the one statement of how each table op runs on
a TPU — as its ``pallas_call`` lowered by Mosaic, or as its **XLA grid
emulation** (the identical kernel body compiled as a loop over the grid,
``emulate=True`` on every kernel entry point; the deletes' loop runs only
the steps that hold a valid lane, ``kernels.delete.live_steps``).  Off TPU
every op runs its emulation, which the CPU parity tests pin bit-for-bit against the
Pallas interpreter.  ``use_pallas='auto'|'always'|'never'`` picks between
that kernel arm and the pure-jnp oracle (``ref.py``): on TPU 'auto' always
takes the kernel arm; off TPU it takes it for batches and tables the VMEM
model admits.

Telemetry: each table-op entry takes a ``telemetry`` flag (trace-time, so
each value is its own program) and, when it is set, returns its usual
outputs plus a trailing ``FilterTelemetry``.  ``telemetry=True`` takes the
kernel arm whatever ``use_pallas`` says, in its emulated form: the counter
planes exist only in the kernel bodies (``_use_kernel`` is where that rule
lives).

Per-op BLOCK sizes come from ``autotune_block`` — the same VMEM footprint
model ``kernel_vmem_bytes`` gives the 'auto' dispatch, inverted: pick the
block that balances the [BLOCK, BLOCK] rank working set (cost grows with
the block) against the per-block whole-table work and launch overhead
(amortized by the block), subject to the op fitting the VMEM budget.

The single dispatch predicate lives in ``_use_kernel`` — the seed had an
operator-precedence bug (``A or (B and C) or D`` instead of
``A or (B and (C or D))``) that silently demoted ``use_pallas='always'`` to
the ref path whenever the VMEM estimate was large; 'always' now ALWAYS takes
the kernel (regression-tested in tests/test_filter_ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.delete import delete_bulk, delete_bulk_adaptive
from repro.kernels.fingerprint import fingerprint_hash, fingerprint_hash_family
from repro.kernels.flash_attention import flash_attention
from repro.kernels.insert import (DEFAULT_EVICT_ROUNDS, insert_bulk,
                                  insert_bulk_adaptive, insert_once)
from repro.kernels.probe import (probe, probe_adaptive,
                                 probe_adaptive_emulated, probe_emulated,
                                 probe_multi)
from repro.kernels.selector import (make_key_planes, make_sel_plane,
                                    report_adapt)
from repro.kernels.stash import (DEFAULT_STASH_SLOTS, make_stash,
                                 stash_delete_ref, stash_occupancy,
                                 stash_probe_ref, stash_spill_ref)
from repro.kernels.telemetry import FilterTelemetry, empty_telemetry

# VMEM residency budget for the filter kernels.  The probe/insert/delete
# BlockSpecs pin the full table per program, and the mutating kernels carry
# extra VMEM-resident working state (see ``kernel_vmem_bytes``); larger
# filters shard first (core.distributed).
VMEM_TABLE_BUDGET = 12 * 2**20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# How each table op runs on a TPU, and the only place that decides it:
#   "mosaic" — its pallas_call, lowered by Mosaic (the TPU Pallas compiler);
#   "xla"    — its emulate=True body, compiled by XLA.
# Mosaic refuses every kernel that gathers from or scatters into the table:
# the probes' row gather on a ref ("Cannot do int indexing on TPU" /
# "Shape mismatch in input, indices and output"), the insert rounds' 2-D
# scatter ("Only 2D gather is supported") and the delete clear round.  Only
# the per-lane hash kernels lower.  tests/test_tpu_compile.py compiles every
# entry for a v5e and fails when an entry stops saying what Mosaic does.
LOWERING = {
    "fingerprint": "mosaic",
    "fingerprint_family": "mosaic",
    "probe": "xla",
    "probe_multi": "xla",
    "insert": "xla",
    "insert_stash": "xla",
    "delete": "xla",
    "probe_adaptive": "xla",
    "insert_adaptive": "xla",
    "delete_adaptive": "xla",
}


def lowering(op: str) -> str:
    """The form ``op`` runs in here: ``LOWERING[op]`` on a TPU, and "xla"
    (the emulation) everywhere else."""
    return LOWERING[op] if _on_tpu() else "xla"


# Budgeted bytes/element for the [block, block] broadcast-compare rank
# (kernels/rank.py).  Bounds: ~1 B/elem if Mosaic streams the iota/compare/
# reduce tiles (the common lowering), ~9 B/elem if the two int32 iotas and
# the bool mask fully materialize.  4 is the engineering estimate pending
# the real-TPU pass (ROADMAP "TPU-hardware validation"); biasing high only
# costs an early fallback to the jnp path, biasing low risks VMEM OOM.
RANK_BYTES_PER_ELEM = 4


def kernel_vmem_bytes(op: str, *, table_bytes: int, block: int,
                      evict_rounds: int = 0, stash_slots: int = 0) -> int:
    """Estimated peak VMEM footprint of one filter-kernel program.

    The model counts a ``(buckets, 4)`` table block at 4 B per slot, but
    VMEM pads the lane dimension to 128, so a resident table block really
    costs 32x that.  The model is kept as it is until a kernel that touches
    the table lowers to Mosaic (see ``LOWERING``).  On a TPU it only feeds
    ``autotune_block``; off TPU it also steers the 'auto' arm, so that
    budgeting reflects what each kernel pins, not just the table:
      * probe  — the table plus two gathered bucket rows per lane;
      * delete — the table plus the [block, block] broadcast-compare rank
        working set (``RANK_BYTES_PER_ELEM``);
      * insert — the table twice over (the eviction rounds' slot-owner
        map is table-shaped), the rank working set, and the 3 per-lane
        eviction-history arrays of width ``evict_rounds``.
    ``stash_slots`` adds the overflow stash's footprint: the aliased
    uint32[2, S] block plus the [block, S] broadcast-compare mask the match
    (probe) / spill (insert) step materializes.
    """
    rank_bytes = RANK_BYTES_PER_ELEM * block * block
    stash_bytes = 8 * stash_slots + block * stash_slots if stash_slots else 0
    if op == "probe":
        return table_bytes + 16 * block + stash_bytes
    if op == "delete":
        return table_bytes + rank_bytes + 16 * block
    if op == "insert":
        return (2 * table_bytes + rank_bytes
                + 3 * 4 * block * max(evict_rounds, 1) + 16 * block
                + stash_bytes)
    raise ValueError(f"unknown filter kernel op {op!r}")


# Pow2 block-size candidates for the autotuner.  128 is the TPU lane width
# (smaller tiles waste the VPU); 8192 keeps the padded-batch overhead and
# the key tiles bounded.
_BLOCK_CANDIDATES = (128, 256, 512, 1024, 2048, 4096, 8192)


@functools.lru_cache(maxsize=256)
def autotune_block(op: str, *, table_bytes: int, evict_rounds: int = 0,
                   stash_slots: int = 0, n_keys: int | None = None) -> int:
    """Per-op kernel BLOCK from the ``kernel_vmem_bytes`` footprint model.

    The fixed ``DEFAULT_BLOCK = 1024`` the kernels shipped with is the
    wrong point for most shapes, in both directions:

      * **probe** has no [BLOCK, BLOCK] rank term — its footprint is table
        + O(BLOCK) — so the biggest block that fits the budget wins (fewer
        grid launches, better key-tile amortization);
      * **insert/delete** pay the rank compare, whose *total* work grows
        linearly with the block (N lanes × BLOCK compares each), so the
        smallest candidate wins — measured on the bench shapes, insert at
        block 128 is ~5x block 1024.  One exception: a batch that fits
        entirely inside a single budget-fitting block takes that block —
        one launch, and a single-block insert reproduces the host
        optimistic round table-for-table (the PR-1 parity contract).

    Candidates are pow2 and must keep the op's ``kernel_vmem_bytes`` inside
    ``VMEM_TABLE_BUDGET`` — the same model 'auto' dispatch budgets with, so
    autotuned blocks can never pick a footprint dispatch would reject.
    That model counts the table at 4 B per slot, not at VMEM's lane-padded
    size (see ``kernel_vmem_bytes``); redoing both waits for a kernel port.
    """
    fits = [b for b in _BLOCK_CANDIDATES
            if kernel_vmem_bytes(op, table_bytes=table_bytes, block=b,
                                 evict_rounds=evict_rounds,
                                 stash_slots=stash_slots)
            <= VMEM_TABLE_BUDGET]
    if not fits:
        return _BLOCK_CANDIDATES[0]
    if op == "probe":
        return fits[-1]
    if n_keys is not None:
        whole = [b for b in fits if b >= n_keys]
        if whole:
            return whole[0]
    return fits[0]


def delete_block(n_keys: int, *, table_bytes: int) -> int:
    """BLOCK of a kernel-arm delete of ``n_keys`` (> 0) lanes on a table of
    ``table_bytes``: the one statement of it, for the delete wrappers below
    and for whoever counts the grid steps the delete runs
    (``kernels.delete.live_steps`` over its lanes padded to the block)."""
    return min(autotune_block("delete", table_bytes=table_bytes,
                              n_keys=n_keys), n_keys)


def _use_kernel(use_pallas: str, *, vmem_bytes: int, n_keys: int,
                telemetry: bool = False) -> bool:
    """True when the kernel arm should run (vs the pure-jnp ref path).

    ``telemetry`` -> kernel arm, unconditionally: the counter planes exist
                only in the kernel bodies.
    'always' -> kernel arm, unconditionally.
    'never'  -> ref path, unconditionally.
    'auto'   -> kernel arm on TPU for every size (its form comes from
                ``LOWERING``; the emulation is not bound by VMEM).  Off TPU,
                kernel arm iff the op's estimated VMEM footprint (see
                ``kernel_vmem_bytes``) fits the budget and the batch has at
                most 65,536 keys.
    """
    if telemetry:
        return True
    if use_pallas == "never":
        return False
    if use_pallas == "always" or _on_tpu():
        return True
    return vmem_bytes <= VMEM_TABLE_BUDGET and n_keys <= 65536


def _pad_to(x: jax.Array, mult: int):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x, n


def _unpad(x: jax.Array, n: int):
    # Skip the slice when the batch needed no padding: an eager x[:n] is a
    # dispatched device op, and on the hot lookup path it is pure overhead.
    return x if x.shape[0] == n else x[:n]


def _unpad_ok(out: tuple, n: int, telemetry: bool) -> tuple:
    """A mutating kernel's outputs with its per-lane ``ok`` (the last
    output, or the one before the trailing ``FilterTelemetry``) unpadded."""
    if telemetry:
        return (*out[:-2], _unpad(out[-2], n), out[-1])
    return (*out[:-1], _unpad(out[-1], n))


def _empty_result(state: tuple, telemetry: bool) -> tuple:
    """An empty batch's outputs: ``state`` unchanged, a zero-lane ``ok``,
    and an all-zero ``FilterTelemetry`` when asked for."""
    out = (*state, jnp.zeros((0,), jnp.bool_))
    return (*out, empty_telemetry()) if telemetry else out


def _probe_telemetry(out) -> tuple:
    """(hit, probe_depth) from an emulated probe -> (hit, FilterTelemetry)."""
    hit, depth = out
    return hit, empty_telemetry()._replace(probe_depth=depth)


def hash_keys(hi: jax.Array, lo: jax.Array, *, fp_bits: int, n_buckets: int,
              use_pallas: str = "auto"):
    """(fp, i1, i2) via the fingerprint kernel (padded to the block size)."""
    if hi.shape[0] == 0 or not _use_kernel(use_pallas, vmem_bytes=0,
                                           n_keys=hi.shape[0]):
        return ref.fingerprint_ref(hi, lo, fp_bits=fp_bits, n_buckets=n_buckets)
    block = min(autotune_block("probe", table_bytes=0), hi.shape[0])
    hi_p, n = _pad_to(hi, block)
    lo_p, _ = _pad_to(lo, block)
    fp, i1, i2 = fingerprint_hash(hi_p, lo_p, fp_bits=fp_bits,
                                  n_buckets=n_buckets, block=block,
                                  interpret=False,
                                  emulate=lowering("fingerprint") == "xla")
    return _unpad(fp, n), _unpad(i1, n), _unpad(i2, n)


def filter_lookup(table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                  fp_bits: int, n_buckets=None, stash=None,
                  use_pallas: str = "auto") -> jax.Array:
    """Bulk membership via the fused probe kernel.

    ``n_buckets``: ACTIVE bucket count when ``table`` is a pow2 buffer
    larger than the live filter (the OCF state); defaults to the full table.
    ``stash``: optional overflow stash — checked inside the same kernel pass
    (or by the jnp ``stash_probe_ref`` on the non-kernel arm), so stashed
    fingerprints answer True exactly like resident ones.
    """
    if hi.shape[0] == 0:
        return jnp.zeros((0,), jnp.bool_)
    stash_slots = 0 if stash is None else stash.shape[1]
    block = min(autotune_block("probe", table_bytes=table.size * 4,
                               stash_slots=stash_slots), hi.shape[0])
    if not _use_kernel(use_pallas,
                       vmem_bytes=kernel_vmem_bytes(
                           "probe", table_bytes=table.size * 4, block=block,
                           stash_slots=stash_slots),
                       n_keys=hi.shape[0]):
        hit = ref.probe_ref(table, hi, lo, fp_bits=fp_bits,
                            n_buckets=n_buckets)
        if stash is not None:
            nb = table.shape[0] if n_buckets is None else n_buckets
            hit = hit | stash_probe_ref(stash, hi, lo, fp_bits=fp_bits,
                                        n_buckets=nb)
        return hit
    hi_p, n = _pad_to(hi, block)
    lo_p, _ = _pad_to(lo, block)
    hit = probe(table, hi_p, lo_p, fp_bits=fp_bits, n_buckets=n_buckets,
                stash=stash, block=block, interpret=False,
                emulate=lowering("probe") == "xla")
    return _unpad(hit, n)


def filter_lookup_multi(tables: jax.Array, hi: jax.Array, lo: jax.Array, *,
                        fp_bits: int, n_buckets=None, stashes=None,
                        use_pallas: str = "auto") -> jax.Array:
    """Bulk membership across K stacked generations -> bool[N].

    ``tables``: uint32[K, buffer_buckets, bucket_size]; ``stashes``:
    optional uint32[K, 2, S]; ``n_buckets`` is the generations' shared
    ACTIVE bucket count.  Kernel arm: ONE fused ``probe_multi`` launch
    whose grid spans all K generations (keys hashed once).  Ref arm: the
    per-generation probe/stash loop — the same answers, 2·K hash passes.
    """
    if hi.shape[0] == 0:
        return jnp.zeros((0,), jnp.bool_)
    k = tables.shape[0]
    per_table_bytes = (tables.size // max(k, 1)) * 4
    stash_slots = 0 if stashes is None else stashes.shape[2]
    block = min(autotune_block("probe", table_bytes=per_table_bytes,
                               stash_slots=stash_slots), hi.shape[0])
    if not _use_kernel(use_pallas,
                       vmem_bytes=kernel_vmem_bytes(
                           "probe", table_bytes=per_table_bytes, block=block,
                           stash_slots=stash_slots),
                       n_keys=hi.shape[0]):
        nb = tables.shape[1] if n_buckets is None else n_buckets
        hit = jnp.zeros(hi.shape, jnp.bool_)
        for g in range(k):
            hit = hit | ref.probe_ref(tables[g], hi, lo, fp_bits=fp_bits,
                                      n_buckets=nb)
            if stashes is not None:
                hit = hit | stash_probe_ref(stashes[g], hi, lo,
                                            fp_bits=fp_bits, n_buckets=nb)
        return hit
    hi_p, n = _pad_to(hi, block)
    lo_p, _ = _pad_to(lo, block)
    hit = probe_multi(tables, hi_p, lo_p, fp_bits=fp_bits,
                      n_buckets=n_buckets, stashes=stashes, block=block,
                      interpret=False,
                      emulate=lowering("probe_multi") == "xla")
    return _unpad(hit, n)


@functools.lru_cache(maxsize=256)
def _probe_plan(fp_bits: int, table_shape: tuple, stash_slots: int):
    """Pinned (block, emulate) for a table shape — the per-call python of
    re-deriving them is measurable on the serving lookup path."""
    table_bytes = table_shape[0] * table_shape[1] * 4
    block = autotune_block("probe", table_bytes=table_bytes,
                           stash_slots=stash_slots)
    return block, lowering("probe") == "xla"


def probe_dispatch(table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                   fp_bits: int, n_buckets=None, stash=None,
                   telemetry: bool = False):
    """``filter_lookup`` with the kernel arm pinned (use_pallas='always'),
    skipping the per-call block/VMEM re-derivation — the one-jit-dispatch
    fast path ``FilterOps.lookup`` takes on the pallas backend.
    -> hit[N], or (hit, FilterTelemetry) with the probe-depth counts."""
    if hi.shape[0] == 0:
        hit = jnp.zeros((0,), jnp.bool_)
        return (hit, empty_telemetry()) if telemetry else hit
    stash_slots = 0 if stash is None else stash.shape[1]
    block, emul = _probe_plan(fp_bits, table.shape, stash_slots)
    if emul or telemetry:
        # No padding needed: the emulated body is gridless.
        if n_buckets is None:
            n_buckets = table.shape[0]
        if telemetry:
            return _probe_telemetry(probe_emulated(
                table, hi, lo, n_buckets, stash, fp_bits=fp_bits,
                telemetry=True))
        return probe_emulated(table, hi, lo, n_buckets, stash,
                              fp_bits=fp_bits)
    b = min(block, hi.shape[0])
    hi_p, n = _pad_to(hi, b)
    lo_p, _ = _pad_to(lo, b)
    hit = probe(table, hi_p, lo_p, fp_bits=fp_bits, n_buckets=n_buckets,
                stash=stash, block=b, interpret=False)
    return _unpad(hit, n)


def multi_prober(tables: jax.Array, *, fp_bits: int, n_buckets=None,
                 stashes=None, use_pallas: str = "auto"):
    """Resolve ``filter_lookup_multi``'s dispatch ONCE for a fixed
    generation stack -> callable ``(hi, lo) -> bool[N]``.

    The streaming ring probes the same K tables for every chunk of a
    batch; re-deriving the block size, VMEM budget, and dispatch arm per
    chunk is measurable overhead on the serving hot path (~15% of a
    4096-key chunk).  The closure pins them, leaving one jitted
    ``probe_multi`` dispatch (plus padding when the tail chunk needs it)
    per call.
    """
    k = tables.shape[0]
    per_table_bytes = (tables.size // max(k, 1)) * 4
    stash_slots = 0 if stashes is None else stashes.shape[2]
    block = autotune_block("probe", table_bytes=per_table_bytes,
                           stash_slots=stash_slots)
    kernel = _use_kernel(use_pallas,
                         vmem_bytes=kernel_vmem_bytes(
                             "probe", table_bytes=per_table_bytes,
                             block=block, stash_slots=stash_slots),
                         n_keys=block)
    if not kernel:
        def ref_probe(hi, lo):
            return filter_lookup_multi(tables, hi, lo, fp_bits=fp_bits,
                                       n_buckets=n_buckets, stashes=stashes,
                                       use_pallas="never")
        return ref_probe
    emul = lowering("probe_multi") == "xla"

    def kernel_probe(hi, lo):
        if hi.shape[0] == 0:
            return jnp.zeros((0,), jnp.bool_)
        b = min(block, hi.shape[0])
        hi_p, n = _pad_to(hi, b)
        lo_p, _ = _pad_to(lo, b)
        hit = probe_multi(tables, hi_p, lo_p, fp_bits=fp_bits,
                          n_buckets=n_buckets, stashes=stashes, block=b,
                          interpret=False, emulate=emul)
        return _unpad(hit, n)

    return kernel_probe


def filter_insert(table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                  fp_bits: int, n_buckets=None, valid=None,
                  evict_rounds: int = 0, stash=None, max_disp: int = 500,
                  use_pallas: str = "auto", schedule: bool = False,
                  donate: bool = False, telemetry: bool = False):
    """Fused bulk insert -> (new_table, placed bool[N]), or
    (new_table, new_stash, placed) when an overflow ``stash`` is attached;
    ``telemetry`` appends a ``FilterTelemetry`` (kick-depth histogram,
    spill/rollback counts, stash fill high-water; padding lanes ride
    ``valid=False`` and are in no counter).

    With ``evict_rounds=0`` this is the PR-1 optimistic single round — the
    fast path for ~95% of a batch, with the caller sweeping the residue.
    With ``evict_rounds>0`` the contended residue is resolved by bounded
    device-side eviction rounds inside the same kernel pass, so the WHOLE
    insert stays on-device (``core.filter_ops.FilterOps.insert``); lanes
    whose chain exceeds the budget spill to the stash when one is attached,
    and only roll back losslessly and report False once the stash is full
    (or when no stash is attached).

    The non-kernel fallback keeps exact scan semantics: optimistic jnp round
    plus the ``lax.scan`` eviction path over the residue (its sequential
    chains bounded by ``max_disp``, the jnp backend's knob); its spill parks
    the *key's own* fingerprint (the scan rolls exhausted chains back),
    while the kernel parks the chain's final carried victim — the two arms
    agree on which lanes succeed and on membership, not on which
    fingerprint of an exhausted chain physically sits in the stash.
    """
    if hi.shape[0] == 0:
        return _empty_result((table,) if stash is None else (table, stash),
                             telemetry)
    if valid is None:
        valid = jnp.ones(hi.shape, bool)
    stash_slots = 0 if stash is None else stash.shape[1]
    block = min(autotune_block("insert", table_bytes=table.size * 4,
                               evict_rounds=evict_rounds,
                               stash_slots=stash_slots,
                               n_keys=hi.shape[0]), hi.shape[0])
    if not _use_kernel(use_pallas,
                       vmem_bytes=kernel_vmem_bytes(
                           "insert", table_bytes=table.size * 4, block=block,
                           evict_rounds=evict_rounds,
                           stash_slots=stash_slots),
                       n_keys=hi.shape[0], telemetry=telemetry):
        table, placed = ref.insert_once_ref(table, hi, lo, fp_bits=fp_bits,
                                            n_buckets=n_buckets, valid=valid)
        if evict_rounds > 0:
            table, ok2 = ref.insert_residue_ref(table, hi, lo,
                                                fp_bits=fp_bits,
                                                n_buckets=n_buckets,
                                                valid=valid & ~placed,
                                                max_disp=max_disp)
            placed = placed | ok2
        if stash is None:
            return table, placed
        nb = table.shape[0] if n_buckets is None else n_buckets
        stash, spilled = stash_spill_ref(stash, hi, lo, valid & ~placed,
                                         fp_bits=fp_bits, n_buckets=nb)
        return table, stash, placed | spilled
    hi_p, n = _pad_to(hi, block)
    lo_p, _ = _pad_to(lo, block)
    valid_p, _ = _pad_to(valid, block)   # pads False: never touches the table
    form = lowering("insert" if stash is None else "insert_stash")
    out = insert_bulk(table, hi_p, lo_p, fp_bits=fp_bits, n_buckets=n_buckets,
                      valid=valid_p, evict_rounds=evict_rounds, stash=stash,
                      block=block, interpret=False, emulate=form == "xla",
                      schedule=schedule, donate=donate, telemetry=telemetry)
    return _unpad_ok(out, n, telemetry)


def filter_delete(table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                  fp_bits: int, n_buckets=None, valid=None, stash=None,
                  use_pallas: str = "auto", donate: bool = False,
                  telemetry: bool = False):
    """Fused bulk delete -> (new_table, deleted bool[N]), or
    (new_table, new_stash, deleted) when an overflow ``stash`` is attached;
    ``telemetry`` appends a ``FilterTelemetry`` counting table- vs
    stash-resolved deletes.

    Device-side first-match-slot clearing via ``kernels.delete``; the
    non-kernel path falls back to the sequential ``lax.scan`` oracle
    (``ref.delete_ref``).  With a stash, lanes that miss the table clear
    their stash entry in a composed jnp pass (``stash_delete`` — the stash
    is tiny, so it never needs the kernel), which is what makes spilled
    keys deletable: table copies go first, exactly like the sequential
    table-then-stash order, because the kernel's rank discipline credits
    earlier duplicate lanes with the resident copies.  Callers must
    pre-verify membership (the OCF keystore does) — blind deletes corrupt
    foreign fingerprints on every cuckoo-filter implementation, kernels
    included.
    """
    if hi.shape[0] == 0:
        return _empty_result((table,) if stash is None else (table, stash),
                             telemetry)
    if valid is None:
        valid = jnp.ones(hi.shape, bool)
    block = delete_block(hi.shape[0], table_bytes=table.size * 4)
    if not _use_kernel(use_pallas,
                       vmem_bytes=kernel_vmem_bytes(
                           "delete", table_bytes=table.size * 4, block=block),
                       n_keys=hi.shape[0], telemetry=telemetry):
        new_table, ok = ref.delete_ref(table, hi, lo, fp_bits=fp_bits,
                                       n_buckets=n_buckets, valid=valid)
    else:
        hi_p, n = _pad_to(hi, block)
        lo_p, _ = _pad_to(lo, block)
        valid_p, _ = _pad_to(valid, block)   # pads False: never touches table
        new_table, ok = delete_bulk(table, hi_p, lo_p, fp_bits=fp_bits,
                                    n_buckets=n_buckets, valid=valid_p,
                                    block=block, interpret=False,
                                    emulate=lowering("delete") == "xla",
                                    donate=donate)
        ok = _unpad(ok, n)
    return _delete_result((new_table,), ok, hi, lo, valid, stash,
                          fp_bits=fp_bits, n_buckets=n_buckets,
                          telemetry=telemetry)


def _delete_result(planes: tuple, ok, hi, lo, valid, stash, *, fp_bits: int,
                   n_buckets, telemetry: bool) -> tuple:
    """A delete's outputs from its cleared ``planes`` and table ``ok``:
    with a ``stash``, lanes that missed the table clear their stash entry
    (the composed jnp pass); with ``telemetry``, the counter plane."""
    if stash is None:
        out = (*planes, ok)
        return (*out, _delete_tm_plane(ok)) if telemetry else out
    nb = planes[0].shape[0] if n_buckets is None else n_buckets
    stash, cleared = stash_delete_ref(stash, hi, lo, valid & ~ok,
                                      fp_bits=fp_bits, n_buckets=nb)
    out = (*planes, stash, ok | cleared)
    if telemetry:
        return (*out, _delete_tm_plane_stash(ok, cleared, stash))
    return out


@jax.jit
def _delete_tm_plane(ok):
    """Counter plane of a stashless delete in ONE fused dispatch — the
    loose ``jnp.sum``/``astype`` calls this replaces each paid a separate
    CPU dispatch, together several times the delete kernel's own cost."""
    return empty_telemetry()._replace(
        table_deletes=jnp.sum(ok).astype(jnp.uint32))


@jax.jit
def _delete_tm_plane_stash(ok, cleared, stash):
    return empty_telemetry()._replace(
        table_deletes=jnp.sum(ok).astype(jnp.uint32),
        stash_deletes=jnp.sum(cleared).astype(jnp.uint32),
        stash_fill_hw=stash_occupancy(stash).astype(jnp.uint32))


# ------------------------------------------------- adaptive dispatch -------
#
# The adaptive filter's state is FOUR planes (fingerprint table + packed
# selector column + two mirror-key planes), all pinned block-resident by the
# selector-aware kernels.  Dispatch reuses the static footprint model with
# the plane-scaled table bytes; there is no separate jnp oracle arm — the
# XLA grid emulation (the same kernel body as one compiled scan) IS the
# fallback, so a batch the VMEM model rejects still runs as compiled XLA
# over HBM instead of dropping to interpret mode.


def _adaptive_plane_bytes(table: jax.Array) -> int:
    """VMEM bytes of the four adaptive planes (fp + khi + klo at table
    shape, plus the packed selector column)."""
    return 3 * table.size * 4 + table.shape[0] * 4


def adaptive_lookup(table: jax.Array, sels: jax.Array, hi: jax.Array,
                    lo: jax.Array, *, fp_bits: int, n_buckets=None,
                    stash=None, use_pallas: str = "auto",
                    telemetry: bool = False):
    """Selector-aware bulk membership -> bool[N], or (hit,
    FilterTelemetry) with the probe-depth counts.

    A slot hits when its stored fingerprint equals the query's family
    fingerprint **under that slot's selector**; stash entries always hold
    selector-0 fingerprints and are matched in the same pass.
    """
    if hi.shape[0] == 0:
        hit = jnp.zeros((0,), jnp.bool_)
        return (hit, empty_telemetry()) if telemetry else hit
    table_bytes = _adaptive_plane_bytes(table)
    stash_slots = 0 if stash is None else stash.shape[1]
    block = min(autotune_block("probe", table_bytes=table_bytes,
                               stash_slots=stash_slots), hi.shape[0])
    kernel = _use_kernel(use_pallas,
                         vmem_bytes=kernel_vmem_bytes(
                             "probe", table_bytes=table_bytes, block=block,
                             stash_slots=stash_slots),
                         n_keys=hi.shape[0])
    if telemetry or not kernel or lowering("probe_adaptive") == "xla":
        if n_buckets is None:
            n_buckets = table.shape[0]
        hi, lo = hi.astype(jnp.uint32), lo.astype(jnp.uint32)
        if telemetry:
            return _probe_telemetry(probe_adaptive_emulated(
                table, sels, hi, lo, n_buckets, stash, fp_bits=fp_bits,
                telemetry=True))
        return probe_adaptive_emulated(table, sels, hi, lo, n_buckets,
                                       stash, fp_bits=fp_bits)
    hi_p, n = _pad_to(hi, block)
    lo_p, _ = _pad_to(lo, block)
    hit = probe_adaptive(table, sels, hi_p, lo_p, fp_bits=fp_bits,
                         n_buckets=n_buckets, stash=stash, block=block,
                         interpret=False)
    return _unpad(hit, n)


def adaptive_insert(table: jax.Array, sels: jax.Array, khi_t: jax.Array,
                    klo_t: jax.Array, hi: jax.Array, lo: jax.Array, *,
                    fp_bits: int, n_buckets=None, valid=None,
                    evict_rounds: int = 0, stash=None,
                    use_pallas: str = "auto", schedule: bool = False,
                    donate: bool = False, telemetry: bool = False):
    """Fused bulk insert over the adaptive planes
    -> (table, sels, khi, klo, placed) or (..., stash, placed), plus a
    trailing ``FilterTelemetry`` with ``telemetry``.

    Same contract as ``filter_insert``; placements and kicks write
    selector-0 entries with the key mirrored into khi/klo, so eviction
    chains re-derive victim geometry exactly and rollback restores all
    four planes verbatim.
    """
    planes = (table, sels, khi_t, klo_t)
    if hi.shape[0] == 0:
        return _empty_result(planes if stash is None else (*planes, stash),
                             telemetry)
    if valid is None:
        valid = jnp.ones(hi.shape, bool)
    table_bytes = _adaptive_plane_bytes(table)
    stash_slots = 0 if stash is None else stash.shape[1]
    # The adaptive chain history carries 6 per-lane arrays (slot coords plus
    # the kicked slot's original fp/sel/key), vs the static kernel's 3 —
    # doubling evict_rounds in the footprint model accounts for them.
    block = min(autotune_block("insert", table_bytes=table_bytes,
                               evict_rounds=2 * evict_rounds,
                               stash_slots=stash_slots,
                               n_keys=hi.shape[0]), hi.shape[0])
    kernel = _use_kernel(use_pallas,
                         vmem_bytes=kernel_vmem_bytes(
                             "insert", table_bytes=table_bytes, block=block,
                             evict_rounds=2 * evict_rounds,
                             stash_slots=stash_slots),
                         n_keys=hi.shape[0], telemetry=telemetry)
    emul = (not kernel) or lowering("insert_adaptive") == "xla"
    hi_p, n = _pad_to(hi, block)
    lo_p, _ = _pad_to(lo, block)
    valid_p, _ = _pad_to(valid, block)   # pads False: never touches planes
    out = insert_bulk_adaptive(*planes, hi_p, lo_p, fp_bits=fp_bits,
                               n_buckets=n_buckets, valid=valid_p,
                               evict_rounds=evict_rounds, stash=stash,
                               block=block, interpret=False, emulate=emul,
                               schedule=schedule, donate=donate,
                               telemetry=telemetry)
    return _unpad_ok(out, n, telemetry)


def adaptive_delete(table: jax.Array, sels: jax.Array, khi_t: jax.Array,
                    klo_t: jax.Array, hi: jax.Array, lo: jax.Array, *,
                    fp_bits: int, n_buckets=None, valid=None, stash=None,
                    use_pallas: str = "auto", donate: bool = False,
                    telemetry: bool = False):
    """Fused bulk delete over the adaptive planes
    -> (table, sels, khi, klo, deleted) or (..., stash, deleted), plus a
    trailing ``FilterTelemetry`` with ``telemetry``.

    Slots are matched under THEIR selector (adapted residents stay
    deletable); clearing zeroes all four planes.  Stash entries hold
    selector-0 fingerprints, so lanes that miss the table compose the same
    jnp ``stash_delete_ref`` pass as the static path.
    """
    planes = (table, sels, khi_t, klo_t)
    if hi.shape[0] == 0:
        return _empty_result(planes if stash is None else (*planes, stash),
                             telemetry)
    if valid is None:
        valid = jnp.ones(hi.shape, bool)
    table_bytes = _adaptive_plane_bytes(table)
    block = delete_block(hi.shape[0], table_bytes=table_bytes)
    kernel = _use_kernel(use_pallas,
                         vmem_bytes=kernel_vmem_bytes(
                             "delete", table_bytes=table_bytes, block=block),
                         n_keys=hi.shape[0], telemetry=telemetry)
    emul = (not kernel) or lowering("delete_adaptive") == "xla"
    hi_p, n = _pad_to(hi, block)
    lo_p, _ = _pad_to(lo, block)
    valid_p, _ = _pad_to(valid, block)   # pads False: never touches planes
    out = delete_bulk_adaptive(*planes, hi_p, lo_p, fp_bits=fp_bits,
                               n_buckets=n_buckets, valid=valid_p,
                               block=block, interpret=False, emulate=emul,
                               donate=donate)
    return _delete_result(out[:-1], _unpad(out[-1], n), hi, lo, valid, stash,
                          fp_bits=fp_bits, n_buckets=n_buckets,
                          telemetry=telemetry)


@functools.partial(jax.jit, static_argnames=("fp_bits", "telemetry"))
def adaptive_report(table: jax.Array, sels: jax.Array, khi_t: jax.Array,
                    klo_t: jax.Array, hi: jax.Array, lo: jax.Array, *,
                    fp_bits: int, n_buckets, valid=None,
                    telemetry: bool = False):
    """Jitted confirmed-false-positive feedback pass
    -> (table, sels, adapted bool[N], resident bool[N]), plus a trailing
    ``FilterTelemetry`` with ``telemetry`` (``selector_bumps`` counts the
    slots whose selector advanced this pass).

    Reports are rare control-plane events; the sequential ``report_adapt``
    scan (exact python-oracle semantics) needs no kernel arm.
    """
    if valid is None:
        valid = jnp.ones(hi.shape, bool)
    out = report_adapt(table, sels, khi_t, klo_t, hi.astype(jnp.uint32),
                       lo.astype(jnp.uint32), valid, fp_bits=fp_bits,
                       n_buckets=n_buckets)
    if not telemetry:
        return out
    return (*out, empty_telemetry()._replace(
        selector_bumps=jnp.sum(out[2]).astype(jnp.uint32)))


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              logit_softcap: float | None = None, scale: float | None = None,
              qpos_start=None, valid_len=None, key_positions=None,
              use_pallas: str = "auto") -> jax.Array:
    """Attention dispatcher.

    TPU: Pallas flash kernel.  XLA path (CPU host / dry-run): window layers
    use the O(S·W) chunked local path; everything else goes through
    blockwise attention (never materializes SxS) — see ref.py docstrings.
    """
    if use_pallas == "always" or (use_pallas == "auto" and _on_tpu()):
        if valid_len is None and qpos_start is None and key_positions is None:
            return flash_attention(q, k, v, causal=causal, window=window,
                                   logit_softcap=logit_softcap, scale=scale,
                                   interpret=not _on_tpu())
    sq, skv = q.shape[2], k.shape[2]
    if (window is not None and causal and valid_len is None
            and key_positions is None and sq == skv
            and sq % window == 0 and sq > window):
        return ref.local_attention(q, k, v, window=window,
                                   logit_softcap=logit_softcap, scale=scale)
    return ref.blockwise_attention(q, k, v, causal=causal, window=window,
                                   logit_softcap=logit_softcap, scale=scale,
                                   qpos_start=qpos_start, valid_len=valid_len,
                                   key_positions=key_positions)


__all__ = ["hash_keys", "filter_lookup", "filter_lookup_multi",
           "filter_insert", "filter_delete", "attention", "fingerprint_hash",
           "fingerprint_hash_family", "probe", "probe_multi", "insert_once",
           "insert_bulk", "delete_bulk", "flash_attention",
           "kernel_vmem_bytes", "autotune_block", "delete_block",
           "VMEM_TABLE_BUDGET",
           "LOWERING", "lowering",
           "DEFAULT_EVICT_ROUNDS", "DEFAULT_STASH_SLOTS", "make_stash",
           "stash_occupancy", "adaptive_lookup", "adaptive_insert",
           "adaptive_delete", "adaptive_report", "make_sel_plane",
           "make_key_planes", "FilterTelemetry", "empty_telemetry"]
