"""TTL-aged filter generations — the streaming membership data plane.

The OCF answers growth with resize+rebuild, which is the right call for a
database index but the wrong one for an unbounded stream: the keystore grows
forever and every rebuild replays it.  Streaming membership (dedup windows,
recent-flow tables, prefix caches with freshness) wants the *multi-level
aging* design of "Don't Thrash: How to Cache Your Hash on Flash": keep K
rotating filter **generations**, insert into the newest, probe all live
ones, and expire by **retiring a whole generation** — an O(1) state drop
instead of per-key deletes.

Layered on the PR-1/PR-3 data plane:

  * every generation is a standard ``FilterState`` + overflow stash pair
    driven through ``FilterOps`` (``insert_spill`` / ``lookup_with_stash``),
    so pallas/jnp dispatch, bounded eviction rounds, and stash spill all
    apply per generation;
  * all generations share one **preallocated buffer pool** (K pow2 tables
    allocated up front and recycled on retirement), so rotation changes no
    array shapes and the jit/kernel cache stays warm for the lifetime of
    the stream;
  * lookups probe every live generation in one jitted device call (the
    FilterOps instance is a static jit argument, so each live-generation
    count compiles once per chunk shape);
  * TTL expiry is **lazy**: an expired generation stops answering lookups
    immediately (it is filtered out of the probe set by timestamp) and its
    buffer is reclaimed on the next rotation/advance — no cleanup thread.

A full-and-stashed insert failure rotates early and retries once in the
fresh generation — the streaming analogue of the OCF's emergency grow,
with bounded (capacity-sized) state instead of a rebuild.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import filter as jfilter
from repro.core.chunking import (collect_chunk_results, key_chunks,
                                 pow2_at_least)
from repro.core.filter_ops import Backend, FilterOps, evict_rounds_for_load
from repro.core.scheduling import dedupe_keys
from repro.kernels.stash import DEFAULT_STASH_SLOTS, stash_occupancy
from repro.streaming.stash import OverflowStash


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Shape and policy of the generation ring."""

    generations: int = 4             # K live generations (the probe fan-out)
    capacity: int = 1 << 14          # item slots per generation
    bucket_size: int = 4
    fp_bits: int = 16
    stash_slots: int = DEFAULT_STASH_SLOTS
    backend: Backend = "auto"
    evict_rounds: Optional[int] = None   # None -> derived from o_max
    o_max: float = 0.85              # rotate when the active table fills past
    stash_high: float = 0.5          # ... or the active stash fills past
    ttl: Optional[float] = None      # seconds a generation stays live
    # Conflict-aware wave scheduling of insert batches + zero-copy buffer
    # donation (the ring owns its pool buffers and never reuses a pre-op
    # table) — see core/scheduling.py and FilterOps.
    schedule: bool = True
    donate: bool = True
    # Host-side lookup dedup (probe one lane per distinct key in a batch).
    # Off by default: all-unique batches would pay the np.unique sort for
    # nothing; dedup-window streams — where repeats ARE the workload —
    # should turn it on.
    dedupe_lookups: bool = False

    def __post_init__(self):
        # Unlike OcfConfig (where stash_slots=0 means "classic OCF, grow on
        # failure"), a generation has no grow path — the stash IS its burst
        # absorber — so a stash-less generation ring is a config error.
        if self.stash_slots < 1:
            raise ValueError(
                "GenerationConfig.stash_slots must be >= 1: generations "
                "absorb eviction storms in the stash (they rotate instead "
                "of growing); use OcfConfig(stash_slots=0) for a stash-"
                "less filter")
        if self.generations < 1:
            raise ValueError("GenerationConfig.generations must be >= 1")

    @property
    def n_buckets(self) -> int:
        return max(1, -(-self.capacity // self.bucket_size))

    def make_filter_ops(self) -> FilterOps:
        rounds = (self.evict_rounds if self.evict_rounds is not None
                  else evict_rounds_for_load(self.o_max))
        return FilterOps(fp_bits=self.fp_bits, backend=self.backend,
                         evict_rounds=rounds, schedule=self.schedule,
                         donate=self.donate)


@dataclasses.dataclass
class GenStats:
    inserts: int = 0
    lookups: int = 0
    rotations: int = 0
    expirations: int = 0             # generations retired by TTL
    spills: int = 0                  # fingerprints parked in stashes
    rotate_retries: int = 0          # inserts that needed the early-rotate


@dataclasses.dataclass
class _Generation:
    state: jfilter.FilterState
    stash: OverflowStash
    born: float
    expires: Optional[float]         # None = no TTL

    def live(self, now: float) -> bool:
        return self.expires is None or now < self.expires


class _BufferPool:
    """K preallocated pow2 table buffers, recycled across generations.

    Retirement hands a zeroed same-shape buffer back, so every generation
    the ring ever creates reuses one of the K original shapes — rotation is
    a jit-cache hit, never a recompile or a foreign allocation.
    """

    def __init__(self, k: int, buffer_buckets: int, bucket_size: int):
        self.shape = (buffer_buckets, bucket_size)
        self._free = [jnp.zeros(self.shape, jnp.uint32) for _ in range(k)]

    def acquire(self) -> jax.Array:
        assert self._free, "buffer pool exhausted (more gens than K?)"
        return self._free.pop()

    def release(self, table: jax.Array) -> None:
        self._free.append(jnp.zeros_like(table))


@functools.partial(jax.jit, static_argnames=("ops",))
def _multi_probe(ops: FilterOps, states, stashes, hi, lo):
    """OR of table+stash membership across the live generations.

    One jitted call per (live-count, chunk-shape) pair — the python loop
    unrolls at trace time, so on device this is a single fused program, not
    K round-trips.
    """
    hit = jnp.zeros(hi.shape, jnp.bool_)
    for state, stash in zip(states, stashes):
        hit = hit | ops.lookup_with_stash(state, stash, hi, lo)
    return hit


class GenerationalFilter:
    """K rotating TTL-aged filter generations with per-generation stashes.

    All ``now`` parameters share ONE clock domain: pass nothing anywhere and
    the wall clock (``time.monotonic``) drives TTLs, or pass your own
    logical timestamps everywhere (tests, replay, event-time streams).  The
    constructor takes the stream's epoch for the same reason — the first
    generation's TTL starts there.
    """

    def __init__(self, config: GenerationConfig | None = None,
                 now: Optional[float] = None, metrics=None):
        """``metrics``: optional ``repro.obs.MetricsRegistry`` — rotation /
        TTL-expiry events become counters; None costs nothing."""
        self.config = config or GenerationConfig()
        self.metrics = metrics
        self.ops = self.config.make_filter_ops()
        buf = pow2_at_least(self.config.n_buckets)
        self.pool = _BufferPool(self.config.generations, buf,
                                self.config.bucket_size)
        self.gens: list[_Generation] = []
        self.stats = GenStats()
        self._last_now: Optional[float] = None
        # identity key -> (prober, source-array refs) for the fused
        # fan-out — see _fanout_prober.
        self._prober_cache: dict = {}
        self._spawn(self._now(now))

    # --------------------------------------------------------- plumbing --

    def _now(self, now: Optional[float]) -> float:
        """Resolve a timestamp, remembering the caller's clock domain.

        Callers on a logical clock pass ``now`` everywhere; the last value
        seen becomes the default for argument-less reads (``len``,
        ``live_generations``), so mixed-domain confusion can't make an
        expired generation look live.  Callers who never pass ``now`` get
        the wall clock throughout.
        """
        if now is not None:
            self._last_now = now
            return now
        return time.monotonic() if self._last_now is None else self._last_now

    def _spawn(self, now: float) -> None:
        cfg = self.config
        state = jfilter.FilterState(
            self.pool.acquire(), jnp.zeros((), jnp.int32),
            jnp.asarray(cfg.n_buckets, jnp.int32))
        ttl = None if cfg.ttl is None else now + cfg.ttl
        self.gens.append(_Generation(state, OverflowStash(cfg.stash_slots),
                                     born=now, expires=ttl))

    def _retire(self, gen: _Generation, *, expired: bool) -> None:
        self.pool.release(gen.state.table)
        if expired:
            self.stats.expirations += 1
            if self.metrics is not None:
                self.metrics.counter("generation_expirations").inc()

    @property
    def active(self) -> _Generation:
        return self.gens[-1]

    def _live(self, now: float) -> list[_Generation]:
        return [g for g in self.gens if g.live(now)]

    _chunks = staticmethod(key_chunks)   # shared contract: core/chunking.py

    # ------------------------------------------------------------- fill --

    @property
    def fill(self) -> float:
        """Active generation's table occupancy (rotation + admission input)."""
        return int(self.active.state.count) / self.config.capacity

    @property
    def stash_fill(self) -> float:
        """Active generation's stash occupancy in [0, 1]."""
        return self.active.stash.fill

    def fills(self) -> tuple[float, float]:
        """(table fill, stash fill) of the active generation in ONE device
        transfer — what the admission controller polls on the scheduler
        intake path (the separate ``fill``/``stash_fill`` properties each
        pay their own sync)."""
        count, occ = self._control_read()
        return count / self.config.capacity, occ / self.config.stash_slots

    @property
    def live_generations(self) -> int:
        return len(self._live(self._now(None)))

    def __len__(self) -> int:
        """Table-resident fingerprints across all generations (approx.)."""
        return sum(int(g.state.count) + g.stash.occupancy for g in self.gens)

    # ---------------------------------------------------------- control --

    def advance(self, now: Optional[float] = None) -> int:
        """Reclaim expired generations' buffers; returns how many retired.

        Lookups already ignore expired generations (lazy expiry) — this
        just returns their buffers to the pool.  The active generation is
        replaced with a fresh one if it expired.
        """
        now = self._now(now)
        dead = [g for g in self.gens if not g.live(now)]
        for g in dead:
            self.gens.remove(g)
            self._retire(g, expired=True)
        if not self.gens:
            self._spawn(now)
        return len(dead)

    def rotate(self, now: Optional[float] = None) -> None:
        """Seal the active generation and open a fresh one (O(1) aging)."""
        now = self._now(now)
        self.advance(now)
        if len(self.gens) >= self.config.generations:
            oldest = self.gens.pop(0)
            self._retire(oldest, expired=False)
        self._spawn(now)
        self.stats.rotations += 1
        if self.metrics is not None:
            self.metrics.counter("generation_rotations").inc()

    def _control_read(self) -> tuple[int, int]:
        """Active generation's (table count, stash occupancy) in ONE
        device->host transfer — the only per-chunk sync the insert path
        pays (the OCF learned the same lesson: per-chunk round-trips
        serialize the whole stream on transfer latency)."""
        gen = self.active
        pair = np.asarray(jnp.stack([
            gen.state.count, stash_occupancy(gen.stash.array)]))
        return int(pair[0]), int(pair[1])

    # ------------------------------------------------------------- ops ---

    def insert(self, keys, now: Optional[float] = None) -> np.ndarray:
        """Insert a batch into the active generation -> ok bool[N].

        Overflow order: table → bounded eviction rounds → stash → early
        rotation + one retry in the fresh generation.  ``ok`` is False only
        when even the retry fails (a chunk larger than a whole generation's
        capacity — a sizing error, not a burst).

        Device discipline: every chunk's ok mask is queued on device and
        pulled back in one stacked transfer after the whole batch; the
        rotation decision costs one combined scalar read per chunk
        (``_control_read``), which doubles as the spill accounting.
        """
        now = self._now(now)
        keys = np.asarray(keys, dtype=np.uint64)
        self.stats.inserts += keys.size
        self.advance(now)
        out = np.ones(keys.size, dtype=bool)
        cfg = self.config
        count, occ = self._control_read()
        oks, ns = [], []
        for hi, lo, valid, n in self._chunks(keys):
            if (count / cfg.capacity >= cfg.o_max
                    or occ / cfg.stash_slots >= cfg.stash_high):
                self.rotate(now)
                count = occ = 0
            prev_occ = occ
            oks.append(self._insert_chunk(hi, lo, valid))
            ns.append(n)
            count, occ = self._control_read()
            self.stats.spills += occ - prev_occ
        idx = (np.flatnonzero(~collect_chunk_results(oks, ns)) if oks
               else np.zeros((0,), np.intp))   # one transfer, all chunks
        if idx.size:
            # Even the stash overflowed: rotate early and retry ONCE in the
            # fresh generation (the streaming analogue of emergency grow).
            self.stats.rotate_retries += idx.size
            self.rotate(now)
            off = 0
            for hi, lo, valid, n in self._chunks(keys[idx]):
                ok = np.asarray(self._insert_chunk(hi, lo, valid))[:n]
                out[idx[off:off + n]] = ok
                off += n
            _count, occ = self._control_read()
            self.stats.spills += occ               # fresh gen started at 0
        return out

    def _insert_chunk(self, hi, lo, valid) -> jax.Array:
        """One device insert into the active generation -> ok (on device)."""
        gen = self.active
        state, stash_arr, ok = self.ops.insert_spill(
            gen.state, gen.stash.array, hi, lo, valid=valid)
        gen.state = state
        gen.stash.array = stash_arr
        return ok

    def _fanout_prober(self, states, stashes):
        """Cached fused fan-out closure over the live generations' tables.

        Stacking K tables + stashes into the fused kernel's [K, ...] inputs
        is an O(K · table_bytes) device copy; the generation set only
        changes on insert/rotate/advance, while a serving workload may
        probe many batches in between.  The cache keys on the live arrays'
        identities (strong refs to the keyed arrays ride along so an id
        can't be recycled while the key is alive) and rebuilds lazily on
        any state change — including donation, which always rebinds
        ``gen.state`` to a fresh array.
        """
        key = tuple((id(s.table), id(a)) for s, a in zip(states, stashes))
        hit = self._prober_cache.get(key)
        if hit is not None:
            return hit[0]
        tables = jnp.stack([s.table for s in states])
        stash_stack = jnp.stack(stashes)
        prober = self.ops.fanout_prober(tables, stash_stack,
                                        n_buckets=states[0].n_buckets)
        if len(self._prober_cache) >= 4:
            # A dict (not one slot) because the serving path alternates
            # lookup() [all live gens] with lookup_active() [active only]
            # per request — one slot would thrash and re-stack every call.
            self._prober_cache.pop(next(iter(self._prober_cache)))
        self._prober_cache[key] = (prober, [s.table for s in states],
                                   list(stashes))
        return prober

    def lookup(self, keys, now: Optional[float] = None) -> np.ndarray:
        """Membership across every live generation -> bool[N]."""
        return self._lookup(keys, now, active_only=False)

    def lookup_active(self, keys, now: Optional[float] = None) -> np.ndarray:
        """Membership in the ACTIVE generation only -> bool[N].

        The promote-on-read primitive of a multi-level design: a key that
        hits overall but misses here lives in an aging generation, and a
        caller that wants it to survive rotation re-inserts it (see
        ``serving.kvcache.GenerationalPrefixIndex.match_prefix``).
        """
        return self._lookup(keys, now, active_only=True)

    def _lookup(self, keys, now: Optional[float], *, active_only: bool
                ) -> np.ndarray:
        now = self._now(now)
        keys = np.asarray(keys, dtype=np.uint64)
        self.stats.lookups += keys.size
        live = self._live(now)
        if active_only:
            live = [g for g in live if g is self.gens[-1]]
        if not live:
            return np.zeros(keys.size, bool)
        if self.config.dedupe_lookups:
            uniq, inverse = dedupe_keys(keys)
        else:
            uniq, inverse = keys, None
        states = tuple(g.state for g in live)
        stashes = tuple(g.stash.array for g in live)
        # pallas: ONE fused kernel per chunk, its grid spanning every live
        # generation (keys hashed once).  jnp: the unrolled per-generation
        # probe loop.  Either way every chunk's hits queue on device and
        # come back in one stacked transfer.
        fused = self.ops.resolve() == "pallas"
        if fused:
            prober = self._fanout_prober(states, stashes)
        hits, ns = [], []
        for hi, lo, _valid, n in self._chunks(uniq, with_valid=False):
            if fused:
                hit = prober(hi, lo)
            else:
                hit = _multi_probe(self.ops, states, stashes, hi, lo)
            hits.append(hit)
            ns.append(n)
        out = collect_chunk_results(hits, ns)
        return out[inverse] if inverse is not None else out
