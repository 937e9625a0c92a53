"""Device-resident telemetry planes for the filter kernels.

The hot path never pays for observability: every table-op entry point
takes a static ``telemetry`` flag and, when it is set, returns a
``FilterTelemetry`` alongside the normal results.  Each value of the flag
is its own compiled program, so the telemetry-off program is the one a
build without this module would run.

All fields are fixed-shape ``uint32`` vectors/scalars so a wave's counters
ride back to the host in the same transfer as its results and merge across
waves with one elementwise op.  ``merge`` is elementwise addition except
for ``stash_fill_hw`` (a high-water mark, merged with ``max``) — that
makes merge associative and commutative, which the property tests pin.

Kick-depth histogram bins are powers of two over the eviction-chain
length: ``0, 1, 2, 3-4, 5-8, 9-16, 17-32, 33+``.  A lane that placed
without kicking lands in bin 0; the open top bin absorbs any
``evict_rounds`` configuration.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

KICK_BINS = 8
PROBE_DEPTHS = 4  # b1-hit, b2-hit, stash-hit, miss

# Inclusive upper edge of each histogram bin except the open-topped last.
KICK_EDGES = (0, 1, 2, 4, 8, 16, 32)
_BIN_EDGES = jnp.asarray(KICK_EDGES, dtype=jnp.uint32)


class FilterTelemetry(NamedTuple):
    """Per-wave device counters; all uint32, fixed shape."""

    kick_hist: jnp.ndarray      # (KICK_BINS,) eviction-chain depth histogram
    probe_depth: jnp.ndarray    # (PROBE_DEPTHS,) lookup hit-depth counts
    stash_spills: jnp.ndarray   # () lanes spilled to the stash
    stash_fill_hw: jnp.ndarray  # () stash occupancy high-water (merge=max)
    rollback_lanes: jnp.ndarray  # () lanes rolled back after a failed chain
    selector_bumps: jnp.ndarray  # () adaptive selector rewrites applied
    overflow_lanes: jnp.ndarray  # () routed-write lanes bounced to the host
    table_deletes: jnp.ndarray  # () deletes resolved in the bucket table
    stash_deletes: jnp.ndarray  # () deletes resolved in the stash


_EMPTY: Optional[FilterTelemetry] = None


def empty_telemetry() -> FilterTelemetry:
    """All-zero counter plane, cached once built outside a trace: jax
    arrays are immutable and no telemetry path donates telemetry
    buffers, so every host-side dispatch can share one instance — 9 fresh
    device_puts per call otherwise dominate the host side of the cheap
    telemetry calls (measured ~0.5 ms on the CPU lookup).  Inside a jit trace
    ``jnp.zeros`` yields tracers, which must never be cached — those
    calls build (and discard) a fresh instance."""
    global _EMPTY
    if _EMPTY is not None:
        return _EMPTY
    u = jnp.uint32
    tm = FilterTelemetry(
        kick_hist=jnp.zeros((KICK_BINS,), u),
        probe_depth=jnp.zeros((PROBE_DEPTHS,), u),
        stash_spills=jnp.zeros((), u),
        stash_fill_hw=jnp.zeros((), u),
        rollback_lanes=jnp.zeros((), u),
        selector_bumps=jnp.zeros((), u),
        overflow_lanes=jnp.zeros((), u),
        table_deletes=jnp.zeros((), u),
        stash_deletes=jnp.zeros((), u),
    )
    if not isinstance(tm.kick_hist, jax.core.Tracer):
        _EMPTY = tm
    return tm


def merge(a: FilterTelemetry, b: FilterTelemetry) -> FilterTelemetry:
    """Fold two waves' counters: add everywhere, max for the high-water."""
    return FilterTelemetry(
        kick_hist=a.kick_hist + b.kick_hist,
        probe_depth=a.probe_depth + b.probe_depth,
        stash_spills=a.stash_spills + b.stash_spills,
        stash_fill_hw=jnp.maximum(a.stash_fill_hw, b.stash_fill_hw),
        rollback_lanes=a.rollback_lanes + b.rollback_lanes,
        selector_bumps=a.selector_bumps + b.selector_bumps,
        overflow_lanes=a.overflow_lanes + b.overflow_lanes,
        table_deletes=a.table_deletes + b.table_deletes,
        stash_deletes=a.stash_deletes + b.stash_deletes,
    )


def kick_histogram(steps: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Histogram eviction-chain lengths into the fixed pow2 bins.

    ``steps`` is the per-lane kick count from the eviction loop carry,
    ``mask`` selects lanes that actually attempted placement.  Fixed
    output shape (KICK_BINS,), so it composes inside jit.  Bin index via
    broadcast-compare against the bin edges (counting edges <= steps) —
    the same ranks-not-sorts idiom the kernels use, no sort, no segment
    ops.
    """
    steps = steps.astype(jnp.uint32)
    idx = jnp.sum(steps[:, None] > _BIN_EDGES[None, :], axis=1)
    onehot = (idx[:, None] == jnp.arange(KICK_BINS)[None, :])
    return jnp.sum(onehot & mask[:, None], axis=0).astype(jnp.uint32)


def probe_depth_counts(h1: jnp.ndarray, h2: jnp.ndarray,
                       hs: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Count lookup lanes by the depth at which they hit.

    ``h1``/``h2``/``hs`` are per-lane bools for a match in the first
    bucket, second bucket, and stash; a lane counts at its *shallowest*
    hit (the order the fused probe short-circuits on TPU is irrelevant —
    this is an accounting convention, not a claim about execution).
    """
    d1 = h1 & valid
    d2 = h2 & ~h1 & valid
    ds = hs & ~h1 & ~h2 & valid
    miss = ~(h1 | h2 | hs) & valid
    return jnp.stack([jnp.sum(d1), jnp.sum(d2), jnp.sum(ds),
                      jnp.sum(miss)]).astype(jnp.uint32)


__all__ = [
    "KICK_BINS", "KICK_EDGES", "PROBE_DEPTHS", "FilterTelemetry",
    "empty_telemetry", "merge", "kick_histogram", "probe_depth_counts",
]
