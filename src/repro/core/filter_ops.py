"""`FilterOps` — the single backend-dispatched filter data plane.

Every consumer of the cuckoo-filter data plane goes through this layer: the
OCF control plane (``core.ocf``), the serving prefix-cache index
(``serving.kvcache``), and the sharded lookup path (``core.distributed``).
One ``backend`` flag flips the whole stack:

  * ``"jnp"``    — the pure-jnp jitted bulk ops (``core.filter``): XLA
                   gather/scatter lookups, optimistic parallel insert round
                   with a mask-driven lax.scan eviction fallback.
  * ``"pallas"`` — the fused kernel data plane (``kernels.probe`` for
                   lookups, ``kernels.insert`` for inserts,
                   ``kernels.delete`` for deletes), each op in the form
                   ``kernels.ops.LOWERING`` gives it on a TPU (Mosaic, or
                   the kernel body compiled by XLA), with hash and probe
                   fused so each key is read once and the active capacity
                   a scalar operand.  Since PR 3 the WHOLE insert stays
                   on-device —
                   the contended residue is resolved by bounded eviction
                   rounds inside the insert kernel (``evict_rounds``), and
                   deletes run through the fused first-match-slot kernel;
                   nothing on this backend touches the lax.scan path.
  * ``"auto"``   — pallas on TPU for every table size, jnp off TPU.

All ops speak (hi, lo) uint32 key pairs and the dynamic-capacity
``FilterState`` (active ``n_buckets`` inside a preallocated pow2 buffer), so
a single FilterOps instance serves every resize the OCF schedule produces
with a warm jit cache.  Both backends implement the *same* hash spec
(``core.hashing`` — the kernels import it directly) and are parity-tested
bit-for-bit against each other and the ``pyfilter`` oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.core import filter as jfilter
from repro.kernels import ops as kops
from repro.kernels import ref as kref

Backend = Literal["jnp", "pallas", "auto"]


def evict_rounds_for_load(load: float) -> int:
    """Eviction-round budget for a target operating load, pow2-rounded.

    Cuckoo insert chains lengthen roughly like 1/(1 - load) as the table
    fills; budgeting ``4 / (1 - load)`` rounds and rounding up to a power
    of two gives the empirically validated points — 32 rounds drains random
    batches at the OCF's default ``o_max = 0.85``, the 0.9-load parity
    tests need 64, and 0.95 maps to 128.  Pow2 rounding keeps the jit cache
    small (the budget is a static kernel parameter).  Clamped to [8, 256]:
    below that chains barely exist, above it the per-lane rollback history
    VMEM cost outgrows what a stash + rotate/grow handles better.
    """
    load = min(max(load, 0.0), 0.97)
    need = 4.0 / (1.0 - load)
    r = 8
    while r < need and r < 256:
        r <<= 1
    return r


def _split_telemetry(out: tuple, telemetry: bool) -> tuple:
    """An op's outputs -> (outputs, ()) or, with ``telemetry``, (outputs
    less the trailing ``FilterTelemetry``, (telemetry,))."""
    return (out[:-1], out[-1:]) if telemetry else (out, ())


@dataclasses.dataclass(frozen=True)
class FilterOps:
    """Backend-dispatched lookup / insert / delete / rebuild entry points.

    ``max_disp`` bounds the sequential eviction chain of the jnp backend;
    ``evict_rounds`` bounds the device-side eviction rounds of the pallas
    insert kernel (its while_loop exits early, so the bound only costs VMEM
    for the per-lane rollback history) and defaults to the budget derived
    from the 0.85 operating load (``evict_rounds_for_load``).  Both exhaust
    the same way: the overflowing key reports False with the table rolled
    back, and the OCF control plane grows + rebuilds from the keystore.

    The ``*_with_stash`` / ``insert_spill`` entry points add the overflow
    stash (``kernels/stash.py``): exhausted chains park in a fixed-size
    device-resident side table instead of failing, and lookups check it in
    the same fused pass — the streaming subsystem's burst escape hatch
    (``repro.streaming``).

    Every stateful op takes ``telemetry``: when it is set, the op runs the
    kernel arm whatever the backend and returns its usual outputs plus a
    trailing ``kernels.telemetry.FilterTelemetry`` (kick-depth histogram,
    probe hit-depth, spill / rollback / delete counters, stash high-water)
    — the kernel arm's answers, bit for bit.  ``delete_table`` takes it
    too: the serving batcher's delete on a stashed static filter runs it.
    """

    fp_bits: int = 16
    max_disp: int = 500
    backend: Backend = "auto"
    # None -> derived from the OCF's default o_max=0.85 operating load
    # (= 32 rounds); pass evict_rounds_for_load(o_max) for other loads, the
    # way OcfConfig.make_filter_ops does.
    evict_rounds: Optional[int] = None
    # Conflict-aware wave scheduling of insert batches (core/scheduling.py):
    # dispatch lanes wave-major by home bucket so blocks meet fewer rank
    # races / eviction rounds.  Off by default — the pre-pass permutes the
    # table layout relative to an unscheduled run, which callers comparing
    # tables bit-for-bit across backends must not enable.  The control
    # planes (OcfConfig / GenerationConfig) turn it on.
    schedule: bool = False
    # Buffer donation: mutating ops consume the caller's table (and stash)
    # buffers so XLA updates them in place instead of copying the pow2
    # buffer every batch.  ONLY for callers that own their buffers and
    # never reuse a pre-op array (the control planes qualify; a benchmark
    # re-inserting into one base state does not).
    donate: bool = False

    def __post_init__(self):
        assert self.backend in ("jnp", "pallas", "auto"), (
            f"unknown filter backend {self.backend!r} "
            "(expected 'jnp' | 'pallas' | 'auto')")
        if self.evict_rounds is None:
            object.__setattr__(self, "evict_rounds",
                               evict_rounds_for_load(0.85))

    # -------------------------------------------------------- dispatch --

    def resolve(self) -> str:
        """Concrete backend ('auto' -> hardware decision).

        On TPU 'auto' is the kernel data plane ("pallas") for every table
        size: each op runs in the form ``kops.LOWERING`` gives it, and the
        XLA emulation is not bound by VMEM, while the jnp arm's writes do
        not fit a deployment-sized table in HBM.  Off TPU 'auto' is "jnp".
        An explicit 'pallas'/'jnp' backend is returned as given.
        """
        if self.backend != "auto":
            return self.backend
        return "pallas" if kops._on_tpu() else "jnp"

    def _use_pallas(self) -> str:
        """The ``kernels.ops`` ``use_pallas`` arm this backend takes."""
        return "always" if self.resolve() == "pallas" else "never"

    def _kernel_arm(self, telemetry: bool) -> bool:
        """True when an op runs the kernel data plane: on the pallas
        backend, and whenever telemetry is asked for (the counter planes
        exist only in the kernel bodies — ``kernels.ops._use_kernel``)."""
        return telemetry or self.resolve() == "pallas"

    # ------------------------------------------------------------- ops --

    def lookup(self, state: jfilter.FilterState, hi: jax.Array,
               lo: jax.Array, telemetry: bool = False):
        """Membership for a batch -> bool[N]."""
        if self._kernel_arm(telemetry):
            return kops.probe_dispatch(state.table, hi, lo,
                                       fp_bits=self.fp_bits,
                                       n_buckets=state.n_buckets,
                                       telemetry=telemetry)
        return jfilter.bulk_lookup(state, hi, lo, fp_bits=self.fp_bits)

    def insert(self, state: jfilter.FilterState, hi: jax.Array,
               lo: jax.Array, valid: Optional[jax.Array] = None,
               telemetry: bool = False):
        """Bulk insert -> (state, ok[N]).

        pallas: ONE fused kernel pass — optimistic rounds plus bounded
        device-side eviction rounds for the contended residue; no lax.scan
        fallback, no host sync.  jnp: the hybrid optimistic-round +
        eviction-chain-scan path.  Either way a key that exhausts its
        budget reports False with the table rolled back (never corrupted).
        """
        if self._kernel_arm(telemetry):
            table, ok, *tm = kops.filter_insert(
                state.table, hi, lo, fp_bits=self.fp_bits,
                n_buckets=state.n_buckets, valid=valid,
                evict_rounds=self.evict_rounds, use_pallas="always",
                schedule=self.schedule, donate=self.donate,
                telemetry=telemetry)
            return jfilter.FilterState(
                table, state.count + jnp.sum(ok, dtype=jnp.int32),
                state.n_buckets), ok, *tm
        # Donation is a kernel-pipeline feature: wrapping the already-jitted
        # hybrid in a donating outer jit measured ~10% SLOWER on CPU (the
        # rewrap costs more than the one table copy it saves), so the jnp
        # arm stays undonated.
        return jfilter.bulk_insert_hybrid(state, hi, lo, fp_bits=self.fp_bits,
                                          max_disp=self.max_disp, valid=valid)

    # ------------------------------------------------- stash-aware ops --

    def lookup_with_stash(self, state: jfilter.FilterState,
                          stash: jax.Array, hi: jax.Array, lo: jax.Array,
                          telemetry: bool = False):
        """Membership against table AND overflow stash -> bool[N].

        pallas: the probe kernel checks the stash in the same fused pass.
        jnp: table probe OR'd with the jnp stash match — identical answers.
        """
        if self._kernel_arm(telemetry):
            return kops.probe_dispatch(state.table, hi, lo,
                                       fp_bits=self.fp_bits,
                                       n_buckets=state.n_buckets,
                                       stash=stash, telemetry=telemetry)
        return kops.filter_lookup(state.table, hi, lo, fp_bits=self.fp_bits,
                                  n_buckets=state.n_buckets, stash=stash,
                                  use_pallas="never")

    def insert_spill(self, state: jfilter.FilterState, stash: jax.Array,
                     hi: jax.Array, lo: jax.Array,
                     valid: Optional[jax.Array] = None,
                     telemetry: bool = False):
        """Bulk insert that spills overflow to the stash
        -> (state, stash, ok[N]).

        ``ok`` goes False only when table eviction budget AND stash are both
        exhausted — the streaming layer answers that with a generation
        rotation instead of the OCF's grow+rebuild.  ``state.count`` tracks
        table-resident fingerprints only; stashed entries are counted by
        ``kops.stash_occupancy`` so occupancy math stays honest.
        """
        spilled_before = kops.stash_occupancy(stash)
        table, new_stash, ok, *tm = kops.filter_insert(
            state.table, hi, lo, fp_bits=self.fp_bits,
            n_buckets=state.n_buckets, valid=valid,
            evict_rounds=self.evict_rounds, stash=stash,
            max_disp=self.max_disp, use_pallas=self._use_pallas(),
            schedule=self.schedule, donate=self.donate, telemetry=telemetry)
        newly_stashed = kops.stash_occupancy(new_stash) - spilled_before
        count = state.count + jnp.sum(ok, dtype=jnp.int32) - newly_stashed
        return jfilter.FilterState(table, count, state.n_buckets), \
            new_stash, ok, *tm

    def delete(self, state: jfilter.FilterState, hi: jax.Array,
               lo: jax.Array, valid: Optional[jax.Array] = None,
               telemetry: bool = False):
        """Verified bulk delete -> (state, ok[N]).

        pallas: the fused first-match-slot kernel (``kernels.delete``).
        jnp: the sequential lax.scan path.  Both rank duplicate keys so the
        k-th duplicate clears the k-th resident copy; callers pre-verify
        membership against the keystore (the OCF control plane does)."""
        if self._kernel_arm(telemetry):
            table, ok, *tm = kops.filter_delete(
                state.table, hi, lo, fp_bits=self.fp_bits,
                n_buckets=state.n_buckets, valid=valid, use_pallas="always",
                donate=self.donate, telemetry=telemetry)
            return jfilter.FilterState(
                table, state.count - jnp.sum(ok, dtype=jnp.int32),
                state.n_buckets), ok, *tm
        return jfilter.bulk_delete(state, hi, lo, fp_bits=self.fp_bits,
                                   valid=valid)

    def rebuild(self, hi: jax.Array, lo: jax.Array, n_buckets: int,
                bucket_size: int, *, buffer_buckets: Optional[int] = None,
                valid: Optional[jax.Array] = None
                ) -> tuple[jfilter.FilterState, jax.Array]:
        """Re-insert a keystore batch into a fresh table (resize path)."""
        state = jfilter.make_state(n_buckets, bucket_size,
                                   buffer_buckets=buffer_buckets)
        return self.insert(state, hi, lo, valid=valid)

    def fanout_prober(self, tables: jax.Array, stashes: jax.Array, *,
                      n_buckets):
        """Dispatch-resolved fan-out closure -> callable (hi, lo) -> bool[N].

        Membership across K stacked generations: ``tables`` is
        uint32[K, buffer_buckets, bucket_size] (the generation ring's pool
        buffers stacked), ``stashes`` uint32[K, 2, S], ``n_buckets`` the
        generations' shared active count.  pallas: ONE fused
        ``probe_multi`` launch whose grid spans every generation (keys
        hashed once); jnp: the per-generation probe/stash loop with
        identical answers.  Block size, VMEM budget, and dispatch arm are
        pinned once — the generation ring caches the closure across a
        batch's chunks (per-chunk re-derivation costs ~15% of a chunk on
        the serving hot path).
        """
        return kops.multi_prober(tables, fp_bits=self.fp_bits,
                                 n_buckets=n_buckets, stashes=stashes,
                                 use_pallas=self._use_pallas())

    # ---------------------------------------------------- adaptive ops --
    #
    # Selector-aware entry points over the four-plane adaptive state
    # (``adaptive.state.AdaptiveState`` — duck-typed here to keep core free
    # of an adaptive import: anything with table/sels/khi/klo/count/
    # n_buckets fields and NamedTuple ``_replace`` works).  The planes ride
    # together through the fused kernels; there is no separate jnp oracle —
    # the XLA grid emulation of the same kernel body is the non-pallas arm,
    # so both backends are bit-for-bit by construction.

    def lookup_adaptive(self, state, hi: jax.Array, lo: jax.Array,
                        stash: Optional[jax.Array] = None,
                        telemetry: bool = False):
        """Selector-aware membership -> bool[N].

        A slot answers under ITS selector, so a repaired slot no longer
        hits the reported query; stash entries are selector-0 and are
        checked in the same pass when attached.
        """
        return kops.adaptive_lookup(
            state.table, state.sels, hi, lo, fp_bits=self.fp_bits,
            n_buckets=state.n_buckets, stash=stash,
            use_pallas=self._use_pallas(), telemetry=telemetry)

    def insert_adaptive(self, state, hi: jax.Array, lo: jax.Array,
                        valid: Optional[jax.Array] = None,
                        stash: Optional[jax.Array] = None,
                        telemetry: bool = False):
        """Bulk insert over the adaptive planes -> (state, ok[N]) or
        (state, stash, ok[N]).

        New entries land as selector-0 slots with the key mirrored into
        khi/klo; kicks reset the victim's selector (its adaptation is the
        price of movement — the standard adaptive-cuckoo trade) and
        rollback restores all four planes verbatim.
        """
        if stash is not None:
            spilled_before = kops.stash_occupancy(stash)
        out, tm = _split_telemetry(kops.adaptive_insert(
            state.table, state.sels, state.khi, state.klo, hi, lo,
            fp_bits=self.fp_bits, n_buckets=state.n_buckets, valid=valid,
            evict_rounds=self.evict_rounds, stash=stash,
            use_pallas=self._use_pallas(),
            schedule=self.schedule, donate=self.donate,
            telemetry=telemetry), telemetry)
        ok = out[-1]
        count = state.count + jnp.sum(ok, dtype=jnp.int32)
        if stash is None:
            table, sels, khi, klo = out[:4]
            return state._replace(table=table, sels=sels, khi=khi, klo=klo,
                                  count=count), ok, *tm
        table, sels, khi, klo, new_stash = out[:5]
        count = count - (kops.stash_occupancy(new_stash) - spilled_before)
        return state._replace(table=table, sels=sels, khi=khi, klo=klo,
                              count=count), new_stash, ok, *tm

    def delete_adaptive(self, state, hi: jax.Array, lo: jax.Array,
                        valid: Optional[jax.Array] = None,
                        stash: Optional[jax.Array] = None,
                        telemetry: bool = False):
        """Verified bulk delete -> (state, ok[N]) or (state, stash, ok[N]).

        Slots match under THEIR selector, so adapted residents stay
        deletable by key; clearing zeroes all four planes.  With a stash,
        lanes that miss the table clear their selector-0 stash entry in the
        composed jnp pass, same order as the static path.
        """
        out, tm = _split_telemetry(kops.adaptive_delete(
            state.table, state.sels, state.khi, state.klo, hi, lo,
            fp_bits=self.fp_bits, n_buckets=state.n_buckets, valid=valid,
            stash=stash, use_pallas=self._use_pallas(),
            donate=self.donate, telemetry=telemetry), telemetry)
        ok = out[-1]
        if stash is None:
            table, sels, khi, klo = out[:4]
            count = state.count - jnp.sum(ok, dtype=jnp.int32)
            return state._replace(table=table, sels=sels, khi=khi, klo=klo,
                                  count=count), ok, *tm
        table, sels, khi, klo, new_stash = out[:5]
        stash_cleared = (kops.stash_occupancy(stash)
                         - kops.stash_occupancy(new_stash))
        count = state.count - jnp.sum(ok, dtype=jnp.int32) + stash_cleared
        return state._replace(table=table, sels=sels, khi=khi, klo=klo,
                              count=count), new_stash, ok, *tm

    def report_false_positive(self, state, hi: jax.Array, lo: jax.Array,
                              valid: Optional[jax.Array] = None,
                              telemetry: bool = False):
        """Feed confirmed false positives back -> (state, adapted[N],
        resident[N]).

        Every slot in a reported key's candidate pair whose stored
        fingerprint collides under that slot's selector is bumped to its
        next family member and rewritten from the mirrored resident key —
        the entry never moves, so no false negative can be introduced.
        ``resident`` flags reports that were actually true positives (never
        repaired); ``adapted`` lanes stop colliding with probability
        1 - 2^-fp_bits per future query.  Stash-resident collisions cannot
        adapt (the stash has no selector) — repeat offenders are the
        reputation tier's job (``adaptive.reputation``).
        """
        table, sels, adapted, resident, *tm = kops.adaptive_report(
            state.table, state.sels, state.khi, state.klo, hi, lo,
            fp_bits=self.fp_bits, n_buckets=state.n_buckets, valid=valid,
            telemetry=telemetry)
        return state._replace(table=table, sels=sels), adapted, resident, \
            *tm

    # --------------------------------------------------- raw-table ops --
    #
    # Stateless entry points over a bare uint32[n_buckets, bucket_size]
    # table (plus optional stash): what ``core.distributed`` runs *inside*
    # shard_map, where there is no FilterState — the shard's table slice IS
    # the state.  Same backend dispatch as the stateful ops; donation is
    # deliberately NOT threaded here (always ``donate=False`` on the inner
    # kernels) because inside a shard_map body the arrays are tracers — the
    # zero-copy update belongs to the *enclosing* jit, which
    # ``distributed_insert``/``distributed_delete`` donate whole.

    def probe_table(self, table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                    n_buckets=None, stash=None) -> jax.Array:
        """Membership probe on a raw table (distributed shards / replicas).

        Same dispatch as ``lookup`` but stateless — ``core.distributed``
        probes stacked per-shard tables inside shard_map with this.  With a
        ``stash`` the shard's overflow entries answer in the same pass
        (fused on the kernel arm), so routed lookups see spilled keys.
        """
        if self.resolve() == "pallas":
            return kops.filter_lookup(table, hi, lo, fp_bits=self.fp_bits,
                                      n_buckets=n_buckets, stash=stash,
                                      use_pallas="always")
        if stash is None:
            return kref.probe_ref(table, hi, lo, fp_bits=self.fp_bits,
                                  n_buckets=n_buckets)
        return kops.filter_lookup(table, hi, lo, fp_bits=self.fp_bits,
                                  n_buckets=n_buckets, stash=stash,
                                  use_pallas="never")

    def insert_table(self, table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                     n_buckets=None, valid: Optional[jax.Array] = None,
                     stash=None):
        """Raw-table bulk insert -> (table, ok[N]) or (table, stash, ok[N]).

        The shard-local write the routed distributed insert runs on-device:
        optimistic rounds + bounded eviction chains + stash spill, scheduled
        when ``self.schedule`` — identical machinery to ``insert`` /
        ``insert_spill`` minus the FilterState bookkeeping (shards count
        occupancy from the table itself).
        """
        return kops.filter_insert(table, hi, lo, fp_bits=self.fp_bits,
                                  n_buckets=n_buckets, valid=valid,
                                  evict_rounds=self.evict_rounds,
                                  stash=stash, max_disp=self.max_disp,
                                  use_pallas=self._use_pallas(),
                                  schedule=self.schedule)

    def delete_table(self, table: jax.Array, hi: jax.Array, lo: jax.Array, *,
                     n_buckets=None, valid: Optional[jax.Array] = None,
                     stash=None, telemetry: bool = False):
        """Raw-table verified delete -> (table, ok[N]) or
        (table, stash, ok[N]).

        Fused first-match-slot clear; with a ``stash``, lanes that miss the
        table clear their spilled entry (table copies first — the
        sequential order), so a burst-parked key is deletable like any
        other.  ``telemetry`` as on the stateful ops.
        """
        return kops.filter_delete(table, hi, lo, fp_bits=self.fp_bits,
                                  n_buckets=n_buckets, valid=valid,
                                  stash=stash, use_pallas=self._use_pallas(),
                                  telemetry=telemetry)
