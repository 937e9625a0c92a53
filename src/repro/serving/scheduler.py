"""Continuous-batching request scheduler with OCF admission control.

The serving-side embodiment of the paper's burst story: requests arrive in
bursts; the scheduler packs them into a fixed decode batch (slots), uses the
OCF prefix index to skip recomputing shared prefixes, and its admission
queue depth is a live congestion signal — the same quantity the EOF
controller integrates.  Host-side control plane; the device work is the
jitted prefill/decode steps from ``engine.py``.

Semantics follow vLLM-style continuous batching, reduced to what a dry-run
framework needs: slot lifecycle (admit → prefill → decode* → finish/evict),
prefix reuse accounting, and backpressure statistics.

Backpressure has two layers since the streaming subsystem landed:
queue depth (always on), and — when an ``AdmissionController``
(``repro.streaming.admission``) is attached — the filter-side congestion
signal (overflow-stash fill + generation fill).  A tripped controller
defers new requests into a side queue that drains once the signal drops
below the hysteresis low-water mark, so a membership-layer burst sheds
load *before* it turns into decode-slot starvation.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import filter as jfilter
from repro.core import hashing
from repro.core.scheduling import dedupe_keys
from repro.kernels import ops as kops
from repro.kernels.telemetry import KICK_EDGES
from repro.serving.engine import greedy_sample, make_decode_step, \
    make_prefill_step
from repro.serving.kvcache import PrefixCacheIndex


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    prefix_hit_blocks: int = 0


@dataclasses.dataclass
class SchedStats:
    admitted: int = 0
    finished: int = 0
    decode_steps: int = 0
    prefills: int = 0
    peak_queue: int = 0
    prefix_blocks_reused: int = 0
    wasted_slot_steps: int = 0    # decode steps with idle slots (burst gaps)
    deferred: int = 0             # requests parked by admission control
    shed_requests: int = 0        # requests dropped by backpressure policy


class ContinuousBatcher:
    """Fixed-slot continuous batcher over a per-slot KV cache.

    One cache per slot keeps the dry-run simple (a paged allocator would
    share pages across slots; the OCF index is the membership layer either
    way).  ``step()`` runs one scheduler tick: fill free slots from the
    queue (prefill), then one fused decode step over the occupied slots.
    """

    def __init__(self, model, params, *, slots: int = 4, cache_len: int = 512,
                 block: int = 32, dtype=jnp.float32,
                 sample_fn: Optional[Callable] = None, index=None,
                 admission=None, backpressure=None):
        """``index``: any PrefixCacheIndex-duck (e.g. the streaming
        ``GenerationalPrefixIndex``); defaults to the OCF-backed one.
        ``admission``: optional ``streaming.AdmissionController`` — when its
        congestion signal trips, ``submit`` parks requests in ``deferred``
        until the signal recedes.  ``backpressure``: optional
        ``engine.BackpressureController`` — a registry-fed admit/defer/shed
        policy consulted BEFORE the filter-side gate; ``shed`` drops the
        request outright (counted in ``stats.shed_requests``)."""
        self.model = model
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.index = index if index is not None else PrefixCacheIndex(
            block=block)
        self.admission = admission
        self.backpressure = backpressure
        self.queue: deque[Request] = deque()
        self.deferred: deque[Request] = deque()
        self.active: dict[int, Request] = {}
        self.pos = np.zeros(slots, dtype=np.int64)
        self.caches = [None] * slots
        self.stats = SchedStats()
        self._prefill = jax.jit(make_prefill_step(model))
        self._decode = jax.jit(make_decode_step(model))
        self._dtype = dtype
        self._sample = sample_fn or greedy_sample
        self._last_tok = [None] * slots

    # ------------------------------------------------------------ intake --

    def submit(self, req: Request) -> bool:
        """Queue a request; returns False when admission control deferred
        it (it stays in ``deferred`` and re-enters on a later tick) or the
        backpressure policy shed it (dropped — the caller must retry)."""
        if self.backpressure is not None:
            decision = self.backpressure.decide()
            if decision == "shed":
                self.stats.shed_requests += 1
                return False
            if decision == "defer":
                self.deferred.append(req)
                self.stats.deferred += 1
                return False
        if self.admission is not None and not self.admission.admit():
            self.deferred.append(req)
            self.stats.deferred += 1
            return False
        self.queue.append(req)
        self.stats.admitted += 1
        self.stats.peak_queue = max(self.stats.peak_queue, len(self.queue))
        return True

    def _drain_deferred(self):
        """Re-admit parked requests while the congestion signal allows.

        Uses the controller's side-effect-free ``peek`` so per-tick polling
        does not inflate its per-request counters.  If the batcher is fully
        starved (everything deferred, nothing queued or decoding), the
        congestion signal can never recede on its own — nothing mutates the
        filter — so age it: reclaim TTL-expired generations, else rotate
        (the same early-rotate policy the filter applies under insert
        pressure); the next tick re-checks.
        """
        # One peek gates the whole drain: nothing in this loop mutates the
        # filter, so the congestion signal (a device read) cannot change
        # between iterations — don't pay one transfer per request.
        if self.deferred and self.admission.peek():
            while self.deferred:
                self.queue.append(self.deferred.popleft())
                self.stats.admitted += 1
                self.stats.peak_queue = max(self.stats.peak_queue,
                                            len(self.queue))
        if self.deferred and not self.queue and not self.active:
            filt = self.admission.filt
            if not filt.advance():
                filt.rotate()

    @property
    def congestion(self) -> float:
        """Queue pressure (+ filter congestion when admission is wired):
        the EOF-style signal, in [0, inf)."""
        q = (len(self.queue) + len(self.deferred)) / max(1, self.slots)
        if self.admission is not None:
            q += self.admission.signal()
        return q

    # ------------------------------------------------------------- tick ---

    def _admit_one(self, slot: int, req: Request):
        hit = self.index.match_prefix(req.prompt)
        req.prefix_hit_blocks = hit
        self.stats.prefix_blocks_reused += hit
        cache = self.model.init_cache(1, self.cache_len, dtype=self._dtype)
        logits, cache = self._prefill(self.params, cache,
                                      jnp.asarray(req.prompt)[None, :])
        self.caches[slot] = cache
        self.pos[slot] = req.prompt.size
        self._last_tok[slot] = self._sample(logits)
        req.out.append(int(self._last_tok[slot][0, 0]))
        self.active[slot] = req
        self.stats.prefills += 1

    def step(self) -> int:
        """One scheduler tick; returns number of live requests decoded."""
        if self.admission is not None and self.deferred:
            self._drain_deferred()
        elif (self.deferred and self.backpressure is not None
                and self.backpressure.decide() == "admit"):
            while self.deferred:
                self.queue.append(self.deferred.popleft())
                self.stats.admitted += 1
                self.stats.peak_queue = max(self.stats.peak_queue,
                                            len(self.queue))
        for slot in range(self.slots):
            if slot not in self.active and self.queue:
                self._admit_one(slot, self.queue.popleft())
        # Dispatch phase: every occupied slot's decode + sample is *queued*
        # on the device with no host sync (jax async dispatch); the per-tick
        # harvest below materializes all sampled tokens in ONE stacked
        # transfer instead of one ``int(tok[0, 0])`` sync per slot — the
        # same dispatch/harvest split the membership submit path
        # (``FilterOpBatcher``) runs at wave granularity.
        live = 0
        ticked: list[tuple[int, Request]] = []
        toks = []
        for slot, req in list(self.active.items()):
            logits, cache = self._decode(self.params, self.caches[slot],
                                         self._last_tok[slot],
                                         jnp.int32(int(self.pos[slot])))
            self.caches[slot] = cache
            self.pos[slot] += 1
            tok = self._sample(logits)
            self._last_tok[slot] = tok
            ticked.append((slot, req))
            toks.append(tok)
            live += 1
        if ticked:
            vals = np.asarray(jnp.concatenate([t[:, 0] for t in toks]))
            for (slot, req), val in zip(ticked, vals):
                req.out.append(int(val))
                if len(req.out) >= req.max_new:
                    self.index.admit(req.prompt)  # publish prefix blocks
                    del self.active[slot]
                    self.caches[slot] = None
                    self.stats.finished += 1
        self.stats.decode_steps += 1
        self.stats.wasted_slot_steps += self.slots - live
        return live

    def run_until_drained(self, max_ticks: int = 10_000) -> SchedStats:
        ticks = 0
        while ((self.queue or self.active or self.deferred)
               and ticks < max_ticks):
            self.step()
            ticks += 1
        return self.stats


# ------------------------------------------------ deferred write pump ----
#
# The routed distributed ops (``core.distributed``) return writes' lanes that
# exceeded their owner shard's all_to_all capacity as a **deferred batch**:
# never attempted, to be resubmitted.  The pump below is the served entry
# point of a sharded filter: lookups, inserts and deletes go through it, and
# it parks deferred write lanes and resubmits them with the SAME hysteresis
# controller the request path uses — deferred keys are a write-side
# admission queue, and resubmitting them while the shards are congested just
# re-defers them (or worse, lands them in saturated stashes).


class ShardedFilterFills:
    """``GenerationalFilter.fills()``-shaped duck over a ShardedFilterState.

    ``AdmissionController`` reads congestion as (generation fill, stash
    fill); for a sharded state the analogous device scalars are aggregate
    table occupancy and aggregate stash occupancy.  Takes a zero-arg getter
    (not a state) because the pump replaces its state every write — the
    controller must always read the CURRENT one.
    """

    def __init__(self, get_state: Callable):
        self._get = get_state

    def fills(self) -> tuple[float, float]:
        state = self._get()
        fill = float(jnp.mean(state.tables != 0))
        stash_fill = (float(jnp.mean(state.stashes[:, 0, :] != 0))
                      if state.stashes is not None else 0.0)
        return fill, stash_fill


_NO_SPAN = contextlib.nullcontext()   # every span without a tracer
_WRITES = ("insert", "delete")


@dataclasses.dataclass
class PumpStats:
    """Lanes by (what, kind): ``offered`` by calls, ``deferred`` (parked by
    routing overflow or while held; a lane can repeat), ``resubmitted`` by
    ``pump``, ``overflowed`` (over a routing capacity: a write deferred, a
    lookup answered "maybe present"), ``acked`` and ``failed`` (a write
    attempted and not acknowledged)."""
    lanes: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    held_ticks: int = 0     # pump ticks the hysteresis gate held the queue

    def total(self, what: str) -> int:
        return sum(v for (w, _kind), v in self.lanes.items() if w == what)

    submitted = property(lambda self: self.total("offered"))
    resubmitted = property(lambda self: self.total("resubmitted"))
    failed = property(lambda self: self.total("failed"))
    inserted = property(lambda self: self.lanes["acked", "insert"])


@dataclasses.dataclass
class ShardCall:
    """One call of the pump.  ``answers`` fills in as lanes are answered;
    ``results`` is set once all are: a lookup at its harvest, a write once
    its deferred lanes have been replayed.  ``deferred`` marks the lanes
    its first attempt parked."""
    kind: str
    n: int
    seq: int
    hi: np.ndarray = dataclasses.field(repr=False)
    lo: np.ndarray = dataclasses.field(repr=False)
    answers: np.ndarray = dataclasses.field(repr=False)
    deferred: np.ndarray = dataclasses.field(repr=False)
    results: Optional[np.ndarray] = None
    unanswered: int = 0


@dataclasses.dataclass
class _Attempt:
    """One routed program in flight: lanes of one kind, from one or more
    calls (``parts``: (call, lane indices) in lane order), at ``pos`` in
    the batch (None: its first lanes)."""
    kind: str
    seq: int
    n: int
    parts: list
    fresh: bool
    device: tuple
    pos: Optional[np.ndarray] = None
    lanes: Optional[tuple] = None   # a traced delete's (hi, lo, valid)


class DeferredWritePump:
    """The served entry point of a sharded filter: routed lookups, inserts
    and deletes over a fixed (mesh, axis, ``ShardedFilterState``), with
    hysteresis-controlled resubmission of deferred writes.

    ``call(kind, keys)`` takes 64-bit keys, splits them on the host, pads
    them to the shard multiple, uploads them once, straight into the mesh's
    ``NamedSharding`` over ``axis``, and enqueues the routed program
    (``core.distributed``).  A call is harvested at the next call, ``flush``,
    ``pump`` or ``run_until_drained``.  Writes' deferred lanes park on the
    host; ``pump`` re-offers them, oldest first and one kind at a time,
    only while the admission controller's congestion signal allows (trip at
    ``high_water``, resume at ``low_water``).  A write never overtakes a
    parked write: parked lanes are replayed first, and while any stay
    parked a new write parks whole, so a delete never runs before the
    insert it follows.  Every write is applied once ``run_until_drained``
    has emptied the queue.  ``capacity_factor`` sizes the writes' exchanges;
    lookups run at ``distributed_lookup``'s own capacity and are never
    parked: an overflowed lane answers "maybe present" and is counted, so
    a small ``capacity_factor`` that defers writes leaves lookups exact at
    fair routing.  Parked batches are padded
    with ``valid=False`` lanes, so resubmission never fabricates writes.

    ``tracer``: a ``repro.obs.TraceRecorder``; each fresh call gets a
    ``shard_dispatch`` span (``shard_prepare``, ``shard_upload``,
    ``distributed.<kind>`` inside) and each harvest a ``shard_harvest``
    (``harvest_wait``, ``harvest_fetch``); a resubmission is a
    ``pump_resubmit``.  Every span carries ``call``, ``kind`` and ``n``.
    After a delete's harvest, outside any span, one ``delete_grid_step``
    instant per grid step its shard-local kernel runs on the busiest shard,
    modelled on the host from the call's lanes
    (``core.distributed.routed_delete_steps``).
    ``metrics``: lane counts as ``routing_<what>_lanes{kind}`` (see
    ``PumpStats``) and ``pump_held_ticks``.
    """

    def __init__(self, mesh, axis: str, state, *, fp_bits: int,
                 admission=None, capacity_factor: float = 2.0,
                 backend: str = "auto", donate: bool = True, metrics=None,
                 tracer=None, route: str = "key"):
        from repro.core import distributed as dist
        from repro.streaming.admission import AdmissionController
        self.fp_bits = fp_bits
        self.capacity_factor = capacity_factor
        self.backend = backend
        self.donate = donate
        self.metrics = metrics
        self.tracer = tracer
        self.route = route
        self._ops = {"lookup": dist.distributed_lookup,
                     "insert": dist.distributed_insert,
                     "delete": dist.distributed_delete}
        self._delete_steps = dist.routed_delete_steps
        self.admission = admission or AdmissionController(
            filt=ShardedFilterFills(lambda: self.state), metrics=metrics)
        self._parked: deque[tuple[ShardCall, np.ndarray]] = deque()
        self._inflight: Optional[_Attempt] = None
        self._seq = 0
        self.stats = PumpStats()
        self.held = False
        self.retarget(mesh, axis, state)

    @property
    def pending(self) -> int:
        """Write lanes parked."""
        return sum(lanes.size for _call, lanes in self._parked)

    def _span(self, name: str, seq: int, kind: str, n: int):
        if self.tracer is None:
            return _NO_SPAN
        return self.tracer.span(name, call=seq, kind=kind, n=n)

    def _count(self, what: str, kind: str, n: int) -> None:
        if n:
            self.stats.lanes[what, kind] += n
            if self.metrics is not None:
                self.metrics.counter(f"routing_{what}_lanes").inc(n,
                                                                  kind=kind)

    # ------------------------------------------ elastic cutover hooks --

    def hold(self):
        """Park every write (fresh calls included) until ``release``.

        The elastic controller brackets a migration with hold/release: a
        routed write issued mid-migration would race the all_to_all
        streams (and target the wrong mesh after cutover), so during the
        window every offered write lane goes straight to the pending queue.
        """
        self.held = True

    def release(self):
        self.held = False

    def retarget(self, mesh, axis: str, state):
        """Point the pump at a new (mesh, axis, state) — the cutover step.

        The parked backlog survives verbatim (host-side uint32 arrays carry
        no mesh commitment) and drains through the new mesh's routed path
        on the next ``pump``.
        """
        self.mesh, self.axis = mesh, axis
        self.state = state
        self.n_shards = mesh.shape[axis]
        self._lanes = NamedSharding(mesh, P(axis))

    # ------------------------------------------------------------ intake --

    def call(self, kind: str, keys) -> ShardCall:
        """Offer one ``lookup`` / ``insert`` / ``delete`` of 64-bit keys ->
        its ``ShardCall`` (answers at harvest)."""
        return self._offer(kind, keys=np.asarray(keys, np.uint64))

    def submit(self, hi, lo):
        """Insert pre-split key halves and harvest at once -> (ok[N],
        deferred[N]) of the first attempt; deferred lanes are parked for
        ``pump``.  While ``held`` the batch parks whole."""
        c = self._offer("insert", hi=np.asarray(hi, np.uint32),
                        lo=np.asarray(lo, np.uint32))
        self.flush()
        return c.answers.copy(), c.deferred.copy()

    def flush(self) -> None:
        """Harvest the call in flight (one ``block_until_ready``)."""
        if self._inflight is not None:
            self._harvest(self._inflight)

    def _offer(self, kind: str, *, keys=None, hi=None, lo=None) -> ShardCall:
        if kind not in self._ops:
            raise ValueError(f"unknown call kind {kind!r}")
        self.flush()
        write = kind in _WRITES
        if write:
            while self._parked and self.pump():
                pass                      # parked writes go first
        n = int(keys.size if keys is not None else hi.size)
        seq, self._seq = self._seq, self._seq + 1
        self._count("offered", kind, n)
        with self._span("shard_dispatch", seq, kind, n):
            with self._span("shard_prepare", seq, kind, n):
                if keys is not None:
                    hi, lo = hashing.key_to_u32_pair_np(keys)
                c = ShardCall(kind, n, seq, hi, lo, np.zeros(n, bool),
                              np.zeros(n, bool), unanswered=n)
                lanes = np.arange(n)
                if write and (self.held or self._parked):
                    c.deferred[:] = True
                    self._park(c, lanes)
                    return c
                padded = self._padded(hi, lo, self._shape(n))
            self._inflight = self._enqueue(kind, seq, [(c, lanes)], padded,
                                           fresh=True)
        return c

    def _shape(self, n: int) -> int:
        """Lanes of a call of ``n`` keys: the next multiple of the shards."""
        return -(-n // self.n_shards) * self.n_shards

    def _park(self, c: ShardCall, lanes: np.ndarray) -> None:
        self._parked.append((c, lanes))
        self._count("deferred", c.kind, lanes.size)

    @staticmethod
    def _padded(hi, lo, size: int):
        """(hi, lo, valid) of ``size`` lanes: the keys, then ``valid=False``
        lanes of key 0."""
        n = hi.size
        valid = np.zeros(size, bool)
        valid[:n] = True
        if size == n:
            return hi, lo, valid
        pad = np.zeros(size - n, np.uint32)
        return np.concatenate([hi, pad]), np.concatenate([lo, pad]), valid

    # ---------------------------------------------------------- pipeline --

    def _enqueue(self, kind: str, seq: int, parts, padded, *,
                 fresh: bool) -> _Attempt:
        """Upload one padded batch and enqueue its routed program; no host
        sync on this path."""
        n = sum(lanes.size for _c, lanes in parts)
        with self._span("shard_upload", seq, kind, n):
            # A lookup has no valid mask: its padding lanes are answered
            # and dropped.
            hi, lo, *valid = jax.device_put(
                padded[:2] if kind == "lookup" else padded, self._lanes)
        with self._span("distributed." + kind, seq, kind, n):
            kw = dict(fp_bits=self.fp_bits, backend=self.backend,
                      route=self.route)
            if kind == "lookup":
                # At the routed lookup's own capacity: ``capacity_factor``
                # sizes the writes' exchanges, whose overflow is replayed.
                device = self._ops[kind](self.mesh, self.axis, self.state,
                                         hi, lo, **kw)
            else:
                self.state, ok, deferred, _ov = self._ops[kind](
                    self.mesh, self.axis, self.state, hi, lo,
                    capacity_factor=self.capacity_factor,
                    donate=self.donate, valid=valid[0], **kw)
                device = (ok, deferred)
        att = _Attempt(kind, seq, n, parts, fresh, device)
        if kind == "delete" and self.tracer is not None:
            att.lanes = padded
        return att

    def _harvest(self, att: _Attempt) -> None:
        """The only sync point: an attempt's answers to the host, deferred
        lanes parked again in their place."""
        with self._span("shard_harvest", att.seq, att.kind, att.n):
            with self._span("harvest_wait", att.seq, att.kind, att.n):
                dev = jax.block_until_ready(att.device)
            with self._span("harvest_fetch", att.seq, att.kind, att.n):
                first, second = jax.device_get(dev)
                if att.kind == "lookup":
                    (c, lanes), = att.parts
                    c.answers[lanes] = first[:lanes.size]
                    self._answered(c, lanes.size)
                    self._count("overflowed", "lookup", int(second.sum()))
                else:
                    if att.pos is not None:
                        first, second = first[att.pos], second[att.pos]
                    self._book_write(att, first, second)
        if att.lanes is not None:
            # After the harvest, outside its span: one marker per grid step
            # the shard-local delete runs on the busiest shard, as the host
            # models it from the call's lanes (``delete.grid_steps``).
            for _ in range(self._delete_steps(
                    self.state, *att.lanes, self.n_shards,
                    fp_bits=self.fp_bits,
                    capacity_factor=self.capacity_factor,
                    backend=self.backend, route=self.route)):
                self.tracer.instant("delete_grid_step", call=att.seq)
        if att is self._inflight:
            self._inflight = None

    def _book_write(self, att: _Attempt, ok, deferred) -> None:
        again, at = [], 0
        for c, lanes in att.parts:
            k = lanes.size
            c_ok, c_dfr = ok[at:at + k], deferred[at:at + k]
            at += k
            done = ~c_dfr
            c.answers[lanes[done]] = c_ok[done]
            if att.fresh:
                c.deferred[lanes] = c_dfr
            if c_dfr.any():
                again.append((c, lanes[c_dfr]))
            self._answered(c, int(done.sum()))
        ok, deferred = ok[:at], deferred[:at]
        n_dfr = int(deferred.sum())
        for what, n in (("deferred", n_dfr), ("overflowed", n_dfr),
                        ("acked", int(ok.sum())),
                        ("failed", int((~ok & ~deferred).sum()))):
            self._count(what, att.kind, n)
        # Back at the head, in order: a fresh write is dispatched only on an
        # empty queue, and a resubmission took the head.
        self._parked.extendleft(reversed(again))

    @staticmethod
    def _answered(c: ShardCall, n: int) -> None:
        c.unanswered -= n
        if c.unanswered == 0:
            c.results = c.answers

    def pump(self) -> int:
        """One resubmission tick -> lanes re-attempted (0 while held).

        Re-offers the oldest parked lanes of one kind, at most as many as
        the oldest call holds, in that call's shape: the routed program a
        fresh call of that shape compiled serves it, so a resubmission
        compiles nothing.  The lanes are dealt round the shards' slices, so
        every source shard's routing capacity takes a share.  Gated by the
        side-effect-free ``peek`` so polling does not inflate the
        controller's per-request counters; a tripped gate holds the parked
        lanes untouched (``held_ticks``) until the congestion signal
        recedes past low_water.
        """
        self.flush()
        if not self._parked:
            return 0
        if self.held or not self.admission.peek():
            self.stats.held_ticks += 1
            if self.metrics is not None:
                self.metrics.counter("pump_held_ticks").inc()
            return 0
        head = self._parked[0][0]
        size = self._shape(head.n)
        parts, n = [], 0
        while (n < size and self._parked
               and self._parked[0][0].kind == head.kind):
            c, lanes = self._parked.popleft()
            if n + lanes.size > size:
                self._parked.appendleft((c, lanes[size - n:]))
                lanes = lanes[:size - n]
            parts.append((c, lanes))
            n += lanes.size
        self._count("resubmitted", head.kind, n)
        with self._span("pump_resubmit", head.seq, head.kind, n):
            i = np.arange(n)
            pos = (i % self.n_shards) * (size // self.n_shards) \
                + i // self.n_shards
            hi, lo = np.zeros(size, np.uint32), np.zeros(size, np.uint32)
            valid = np.zeros(size, bool)
            hi[pos] = np.concatenate([c.hi[lanes] for c, lanes in parts])
            lo[pos] = np.concatenate([c.lo[lanes] for c, lanes in parts])
            valid[pos] = True
            att = self._enqueue(head.kind, head.seq, parts, (hi, lo, valid),
                                fresh=False)
            att.pos = pos
            self._harvest(att)
        return n

    def run_until_drained(self, *, max_ticks: int = 100,
                          on_held=None) -> PumpStats:
        """Harvest, then pump until nothing is parked (or ``max_ticks``):
        every write offered so far is then applied.

        ``on_held``: optional callback invoked on each held tick — the hook
        where a control plane relieves congestion (rotate a generation,
        grow the shards, age the stash); without one a tripped gate over a
        static filter would hold forever, so the loop stops early when
        holding makes no progress and nothing external intervenes.
        """
        self.flush()
        for _ in range(max_ticks):
            if not self.pending:
                break
            if self.pump() == 0 and on_held is None:
                break
            if on_held is not None and self.admission.tripped:
                on_held(self)
        return self.stats


# --------------------------------------- membership-op submit path ------
#
# The latency side of the serving story.  ``ContinuousBatcher`` schedules
# decode slots; the filter traffic it fronts (prefix-index probes, the SLO
# harness's scenario replay) arrives as *waves* of homogeneous membership
# ops.  The batcher below is the wave-granular submit path: one wave is
# prepared host-side (pad to a fixed shape, hash split, optional lookup
# dedup), dispatched to the device through ``FilterOps``, and harvested —
# ``jax.block_until_ready`` ONLY at harvest.  In double-buffered mode the
# harvest of wave k happens *after* wave k+1 has been prepared and
# dispatched, so host prep overlaps device execution and the scheduler,
# not host sync, sets the latency floor.  Both modes issue the identical
# device-call sequence in the identical order, so their results (and the
# filter state they leave behind) are bit-for-bit equal — the oracle
# parity tests in tests/test_slo.py pin this.


# Wave latency histogram edges (µs) for the metrics registry — spans the
# sync-path microbench floor through admission-parked closed-loop tails.
LATENCY_BUCKETS_US = (50.0, 100.0, 200.0, 500.0, 1_000.0, 2_000.0,
                      5_000.0, 10_000.0, 25_000.0, 50_000.0, 100_000.0)


@jax.jit
def _fill_read(count, stash):
    """A fill snapshot, (count, stash occupancy), packed as int32[2]: one
    program to compute it and one copy to bring it to the host."""
    occ = jnp.int32(0) if stash is None else kops.stash_occupancy(stash)
    return jnp.stack([count, occ])


@jax.jit
def _table_delete_fill(count, stash_before, stash, ok):
    """A table delete's count and its fill snapshot, in one program.
    ``ok`` counts table AND stash clears; count tracks the table."""
    occ = kops.stash_occupancy(stash)
    count = (count - jnp.sum(ok, dtype=jnp.int32)
             + (kops.stash_occupancy(stash_before) - occ))
    return count, jnp.stack([count, occ])


@dataclasses.dataclass
class OpWave:
    """One submitted wave and its timing: the recorder's unit of sample.

    ``latency_us`` spans offered -> results-materialized, so a wave parked
    by admission control carries its queueing delay (closed-loop latency,
    not bare kernel time)."""
    kind: str
    n: int
    submit_s: float
    seq: int = 0                  # the batcher's wave sequence number
    done_s: float = 0.0
    deferred_ticks: int = 0       # submit ticks spent parked by admission
    results: Optional[np.ndarray] = None
    # harvest internals: device refs + result slicing metadata
    _device: tuple = dataclasses.field(default=(), repr=False)
    _n_probe: int = 0
    _inverse: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)

    @property
    def latency_us(self) -> float:
        return (self.done_s - self.submit_s) * 1e6


@dataclasses.dataclass
class BatcherStats:
    waves: int = 0                # waves offered via submit()
    ops: int = 0                  # real (non-padding) lanes offered
    harvests: int = 0
    deferred_waves: int = 0       # insert waves parked by admission
    held_ticks: int = 0           # drain attempts the gate held the queue
    shed_ops: int = 0             # lanes still parked when drain gave up
    deduped_lanes: int = 0        # lookup lanes collapsed by dedup
    fill_reads: int = 0           # waves that read a new fill snapshot


class FilterOpBatcher:
    """Double-buffered wave submit path over a ``FilterOps`` data plane.

    Works over either state family:

      * ``core.filter.FilterState`` (+ optional overflow stash) — lookup /
        insert / delete through the static-filter entry points;
      * ``adaptive.state.AdaptiveState`` (detected by its ``sels`` plane)
        — the selector-aware entry points, plus the ``report`` kind
        feeding confirmed false positives back.

    Waves are padded to ``wave_slots`` (key 0, ``valid=False``) so every
    (kind, state-family) pair compiles exactly once.  ``submit`` returns
    the ``OpWave`` immediately; ``wave.results`` is populated at harvest —
    the next submit (double-buffered) or before submit returns (sync).
    Call ``flush()`` to force the in-flight wave out (the closed-loop
    feedback point: adversarial report waves need the previous lookup's
    results).

    Admission coupling: with an ``AdmissionController`` attached (or an
    ``AdmissionConfig``, from which one is built over this batcher's own
    ``fills()`` duck), insert waves are gated by the hysteresis signal —
    tripped inserts park in a deferred queue that retries on later submits
    / ``drain()``.  Deletes and lookups bypass the gate (deletes *relieve*
    congestion; probes don't add occupancy).  ``fills()`` reports the
    occupancy snapshot as of the last harvest — polling it costs no
    device sync, so the controller can gate every wave without stalling
    the pipeline.  The snapshot is read only on waves whose op replaced
    the state's count or the stash (one program, one copy at harvest);
    any other wave (every lookup) left both as they were, so the snapshot
    the last such wave read still holds and the wave reads none.

    ``double_buffer="auto"`` (the default) resolves per host: overlap
    only pays when device work and host prep run on different silicon, so
    it picks the async path on real accelerators and on multi-core CPU
    hosts (XLA's compute pool and the numpy prep genuinely interleave),
    and the sync path on a single-core CPU host — there the "device" IS
    the host core, every pipelined wave just queues behind the previous
    one, and per-wave latency doubles for zero wall-clock gain.  Both
    paths issue the identical device-call sequence in the identical
    order, so the choice is bit-for-bit invisible to results.
    """

    def __init__(self, ops, state, *, stash: Optional[jax.Array] = None,
                 wave_slots: int = 512, double_buffer="auto",
                 dedupe_lookups: bool = True, admission=None,
                 clock: Callable[[], float] = time.perf_counter,
                 telemetry: bool = False, metrics=None, tracer=None):
        """Observability kwargs (all default-off; the off path issues the
        identical device-call sequence as a batcher built without them):

        ``telemetry``: call the ``FilterOps`` entries with
        ``telemetry=True`` so every wave also returns a device-computed
        ``FilterTelemetry`` (kick-depth histogram, probe hit-depth,
        spill/rollback counters); the counters ride ``wave._device`` and
        materialize in the SAME single ``block_until_ready`` as the
        results.  ``metrics``: a
        ``repro.obs.MetricsRegistry`` receiving wave timings + counters
        (auto-created when ``telemetry`` is on and none is given).
        ``tracer``: a ``repro.obs.TraceRecorder``; dispatch and harvest
        get Chrome-trace spans."""
        self.ops = ops
        self.state = state
        self.stash = stash
        self.telemetry = bool(telemetry)
        if self.telemetry and metrics is None:
            from repro.obs import MetricsRegistry
            metrics = MetricsRegistry()
        self.metrics = metrics
        self.tracer = tracer
        self.wave_slots = int(wave_slots)
        if double_buffer == "auto":
            double_buffer = (jax.default_backend() != "cpu"
                             or (os.cpu_count() or 1) > 1)
        self.double_buffer = bool(double_buffer)
        self.dedupe_lookups = bool(dedupe_lookups)
        self._clock = clock
        self._adaptive = hasattr(state, "sels")
        self.capacity = int(state.n_buckets) * state.table.shape[1]
        self.stash_slots = 0 if stash is None else int(stash.shape[1])
        self._take_fills(_fill_read(state.count, stash))
        if admission is not None and not hasattr(admission, "admit"):
            from repro.streaming.admission import AdmissionController
            admission = AdmissionController(filt=self, config=admission,
                                            metrics=self.metrics)
        self.admission = admission
        self._inflight: Optional[OpWave] = None
        self._deferred: deque[tuple[OpWave, np.ndarray]] = deque()
        self.stats = BatcherStats()

    # ----------------------------------------------------------- intake --

    def submit(self, kind: str, keys) -> OpWave:
        """Offer one wave -> its ``OpWave`` (results pending until harvest).

        Parked insert waves are retried (FIFO) before the new wave, so
        admission never reorders writes relative to each other."""
        keys = np.ascontiguousarray(np.asarray(keys, np.uint64))
        wave = OpWave(kind=kind, n=int(keys.size), submit_s=self._clock(),
                      seq=self.stats.waves)
        self.stats.waves += 1
        self.stats.ops += wave.n
        self._retry_deferred()
        if (kind == "insert" and self.admission is not None
                and not self.admission.admit()):
            self._deferred.append((wave, keys))
            self.stats.deferred_waves += 1
            if self.metrics is not None:
                self.metrics.counter("filter_deferred_waves").inc()
            return wave
        self._launch(wave, keys)
        return wave

    def flush(self) -> None:
        """Materialize the in-flight wave (one ``block_until_ready``)."""
        if self._inflight is not None:
            self._harvest(self._inflight)

    def drain(self, *, max_ticks: int = 100, on_held=None) -> int:
        """Retry parked waves until none remain (or ``max_ticks``), then
        flush -> number of ops still parked (shed).

        ``on_held``: callback invoked when the gate holds with nothing
        in flight to relieve it — the hook where a control plane ages or
        deletes; without one the loop stops once holding makes no
        progress, and the remainder counts as shed load."""
        for _ in range(max_ticks):
            if not self._deferred:
                break
            before = len(self._deferred)
            self._retry_deferred()
            if len(self._deferred) == before:
                self.stats.held_ticks += 1
                if self.metrics is not None:
                    self.metrics.counter("filter_held_ticks").inc()
                if on_held is None:
                    break
                on_held(self)
        self.flush()
        shed = sum(keys.size for _, keys in self._deferred)
        self.stats.shed_ops += shed
        if shed and self.metrics is not None:
            self.metrics.counter("filter_shed_ops").inc(shed)
        return shed

    def fills(self) -> tuple[float, float]:
        """(table fill, stash fill) at the LAST harvest — the
        ``GenerationalFilter.fills()`` duck, sync-free by construction."""
        return self._fill_snapshot

    def _take_fills(self, fill) -> None:
        """Set the snapshot from a packed (count, stash occupancy)."""
        count, occ = np.asarray(fill).tolist()
        self._fill_snapshot = (
            float(count) / max(1, self.capacity),
            float(occ) / self.stash_slots if self.stash_slots else 0.0)

    # --------------------------------------------------------- pipeline --

    def _retry_deferred(self) -> None:
        while self._deferred:
            if self.admission is not None and not self.admission.peek():
                for parked, _ in self._deferred:
                    parked.deferred_ticks += 1
                break
            wave, keys = self._deferred.popleft()
            self._launch(wave, keys)

    def _span(self, name: str, wave: OpWave):
        """Trace span of one step of ``wave``, host-side only and never a
        device sync.  With no tracer: the shared no-op, with no clock read
        and no arguments built."""
        if self.tracer is None:
            return _NO_SPAN
        return self.tracer.span(name, wave=wave.seq, kind=wave.kind,
                                n=wave.n)

    def _launch(self, wave: OpWave, keys: np.ndarray) -> None:
        prev = self._inflight
        with self._span("wave_dispatch", wave):
            self._dispatch(wave, keys)  # overlaps prev's device exec
        self._inflight = wave
        if prev is not None:
            self._harvest(prev)
        if not self.double_buffer:
            self._harvest(wave)

    def _prepare(self, wave: OpWave, keys: np.ndarray):
        """Host-side wave prep: dedup (lookups), pad, hash split, upload."""
        with self._span("wave_prepare", wave):
            if wave.kind == "lookup" and self.dedupe_lookups:
                keys, wave._inverse = dedupe_keys(keys)
                if wave._inverse is not None:
                    self.stats.deduped_lanes += wave.n - keys.size
            n = keys.size
            assert n <= self.wave_slots, (n, self.wave_slots)
            wave._n_probe = n
            padded = np.zeros(self.wave_slots, np.uint64)
            padded[:n] = keys
            hi, lo = hashing.key_to_u32_pair_np(padded)
            valid = np.zeros(self.wave_slots, bool)
            valid[:n] = True
        with self._span("wave_upload", wave):
            return jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(valid)

    def _op(self, wave: OpWave, entry: str, *args, **kwargs):
        """One ``FilterOps`` call: ``entry``, with ``telemetry=True`` when
        telemetry is on -> (the entry's outputs, ``FilterTelemetry`` or
        None).  The telemetry rides ``wave._device`` so the harvest
        materializes counters and results in the SAME single
        ``block_until_ready`` — telemetry adds no extra sync points."""
        with self._span("filterops." + entry, wave):
            if not self.telemetry:
                return getattr(self.ops, entry)(*args, **kwargs), None
            *out, tm = getattr(self.ops, entry)(*args, telemetry=True,
                                                **kwargs)
        return (out[0] if len(out) == 1 else tuple(out)), tm

    def _dispatch(self, wave: OpWave, keys: np.ndarray) -> None:
        """Queue the wave's device work; grab (results, fill snapshot or
        None [, telemetry]) refs for the harvest.  No host sync on this
        path."""
        hi, lo, valid = self._prepare(wave, keys)
        state, stash = self.state, self.stash
        table_delete = False
        if wave.kind == "lookup":
            if self._adaptive:
                res, tm = self._op(wave, "lookup_adaptive", state, hi, lo,
                                   stash=stash)
            elif stash is not None:
                res, tm = self._op(wave, "lookup_with_stash", state, stash,
                                   hi, lo)
            else:
                res, tm = self._op(wave, "lookup", state, hi, lo)
        elif wave.kind == "insert":
            if self._adaptive and stash is not None:
                (self.state, self.stash, res), tm = self._op(
                    wave, "insert_adaptive", state, hi, lo, valid=valid,
                    stash=stash)
            elif self._adaptive:
                (self.state, res), tm = self._op(
                    wave, "insert_adaptive", state, hi, lo, valid=valid)
            elif stash is not None:
                (self.state, self.stash, res), tm = self._op(
                    wave, "insert_spill", state, stash, hi, lo, valid=valid)
            else:
                (self.state, res), tm = self._op(wave, "insert", state, hi,
                                                 lo, valid=valid)
        elif wave.kind == "delete":
            if self._adaptive:
                out, tm = self._op(wave, "delete_adaptive", state, hi, lo,
                                   valid=valid, stash=stash)
                if stash is not None:
                    self.state, self.stash, res = out
                else:
                    self.state, res = out
            elif stash is not None:
                (table, self.stash, res), tm = self._op(
                    wave, "delete_table", state.table, hi, lo,
                    n_buckets=state.n_buckets, valid=valid, stash=stash)
                self.state = state._replace(table=table)
                table_delete = True
            else:
                (self.state, res), tm = self._op(wave, "delete", state, hi,
                                                 lo, valid=valid)
        elif wave.kind == "report":
            if not self._adaptive:
                raise ValueError("'report' waves need an AdaptiveState")
            (self.state, res, _resident), tm = self._op(
                wave, "report_false_positive", state, hi, lo, valid=valid)
        else:
            raise ValueError(f"unknown wave kind {wave.kind!r}")
        with self._span("wave_occupancy", wave):
            fill = None
            if table_delete:
                count, fill = _table_delete_fill(state.count, stash,
                                                 self.stash, res)
                self.state = self.state._replace(count=count)
            elif (self.state.count is not state.count
                  or self.stash is not stash):
                fill = _fill_read(self.state.count, self.stash)
            if fill is not None:
                self.stats.fill_reads += 1
                if self.metrics is not None:
                    self.metrics.counter("filter_fill_reads").inc(
                        kind=wave.kind)
        wave._device = (res, fill) + ((tm,) if self.telemetry else ())

    def _harvest(self, wave: OpWave) -> None:
        """The ONLY sync point: materialize one wave's device refs."""
        with self._span("wave_harvest", wave):
            with self._span("harvest_wait", wave):
                dev = jax.block_until_ready(wave._device)
            with self._span("harvest_fetch", wave):
                res, fill, *tm = dev
                out = np.asarray(res)[:wave._n_probe]
                wave.results = out[wave._inverse] \
                    if wave._inverse is not None else out
                if fill is not None:
                    self._take_fills(fill)
        wave._device = ()
        wave.done_s = self._clock()
        self.stats.harvests += 1
        if wave is self._inflight:
            self._inflight = None
        if self.metrics is not None:
            self._record_wave(wave, tm[0] if tm else None)

    # ------------------------------------------------------ observability --

    def _record_wave(self, wave: OpWave, tm) -> None:
        """Fold one harvested wave into the metrics registry.  ``tm`` is a
        ``FilterTelemetry`` (already materialized) or None when the batcher
        runs host metrics without device counter planes."""
        m = self.metrics
        m.counter("filter_waves").inc(kind=wave.kind)
        m.counter("filter_wave_ops").inc(wave.n, kind=wave.kind)
        m.histogram("filter_wave_latency_us",
                    buckets=LATENCY_BUCKETS_US).observe(wave.latency_us,
                                                        kind=wave.kind)
        m.record_wave({"kind": wave.kind, "n": wave.n,
                       "latency_us": wave.latency_us,
                       "deferred_ticks": wave.deferred_ticks})
        if tm is None:
            return
        # One bulk device->host pull for the whole counter plane — the
        # per-field int()/asarray conversions each pay a jax->numpy hop,
        # which at wave rate was the biggest slice of telemetry overhead.
        tm = type(tm)(*jax.device_get(tuple(tm)))
        if wave.kind == "insert":
            m.histogram("filter_kick_depth",
                        buckets=KICK_EDGES).observe_counts(
                [int(c) for c in tm.kick_hist])
        for depth, cnt in zip(("b1", "b2", "stash", "miss"),
                              tm.probe_depth):
            if cnt:
                m.counter("filter_probe_depth").inc(int(cnt), depth=depth)
        for name, val in (("filter_stash_spills", tm.stash_spills),
                          ("filter_rollback_lanes", tm.rollback_lanes),
                          ("filter_selector_bumps", tm.selector_bumps),
                          ("filter_overflow_lanes", tm.overflow_lanes),
                          ("filter_table_deletes", tm.table_deletes),
                          ("filter_stash_deletes", tm.stash_deletes)):
            v = int(val)
            if v:
                m.counter(name).inc(v)
        m.gauge("filter_stash_fill_hw").set_max(int(tm.stash_fill_hw))
