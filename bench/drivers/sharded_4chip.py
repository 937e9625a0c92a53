"""Four chips, closed loop, through the served entry point of a sharded
filter.

Set-up: every shard loaded on its own chip from the seed
(``bench.shard_placement``), empty per-shard stashes, and one
``DeferredWritePump`` over the ``ShardedFilterState`` as the program
builds it (its defaults: buffer donation on, ``backend="auto"``; the
configuration's ``capacity_factor``).  Two rounds of an insert, a delete
and a lookup call of the window's shape warm up the window's programs on
the placed state (warm-up keys inserted, then deleted).

The window issues the calls of the closed loop (``bench.closedloop``) one
at a time: each call goes to the pump, which is then run until drained, and
the harness stamps the call done when its answers are on the host.  A
traced window ends after one cycle of the mix: the profiler records every
op of the emulated writes' loops, over a million events for one routed
insert of 32,768 keys, so a traced window of seconds takes minutes to
write out and read back.

After the window, through the same pump and programs (calls of the same
shape, the last one filled up with absent keys): every window insert still
live is read back; ``check_sample`` live set-up members, as many absent keys
and as many keys the window deleted are looked up; the tables and stashes of
every shard are counted, and every answer is held to the reference.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import closedloop, harness, openloop, reference, shard_placement
from bench import keys as K
from bench import traffic

AXIS = "data"
WARM_BASE = 1 << 31        # absent-class indices of the warm-up probes
CHECK_BASE = 1 << 30       # absent-class indices of the read-back sample


def _entry_point():
    from repro.serving.scheduler import DeferredWritePump
    if not hasattr(DeferredWritePump, "call"):
        raise RuntimeError("the program has no served entry point for "
                           "lookups, inserts and deletes on a sharded "
                           "filter (DeferredWritePump.call)")
    return DeferredWritePump


def build(ctx, log):
    pump_cls = _entry_point()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import ShardedFilterState
    from repro.launch.mesh import make_mesh
    cfg = ctx.cell.config
    devs = harness.devices_for(ctx.cell.chips, ctx.require_tpu)
    n = cfg["n_shards"]
    if n != len(devs):
        raise ValueError(f"{n} shards on {len(devs)} chips: one shard a chip")
    mesh = make_mesh((n,), (AXIS,), devs)
    t = time.perf_counter()
    tables, placed = shard_placement.load_tables(
        mesh, AXIS, cfg["n_buckets"], cfg["bucket_size"], seed=ctx.seed,
        load=cfg["load"], chunk=cfg["setup_chunk"], fp_bits=cfg["fp_bits"])
    n_placed = int(placed.sum())
    load = n_placed / (n * cfg["n_buckets"] * cfg["bucket_size"])
    log(f"set-up: {n_placed} of {placed.size} offered keys placed on {n} "
        f"shards (load {load:.5f}) in {time.perf_counter() - t:.3f} s, at "
        f"{time.perf_counter() - ctx.t_start:.3f} s")
    stashes = jax.jit(lambda: jnp.zeros((n, 2, cfg["stash_slots"]),
                                        jnp.uint32),
                      out_shardings=NamedSharding(mesh, P(AXIS)))()
    state = ShardedFilterState(tables, stashes, cfg["n_buckets"])
    tracer = None
    if ctx.trace:
        from repro.obs.trace import TraceRecorder
        tracer = TraceRecorder(jax_profiler=True)
    pump = pump_cls(mesh, AXIS, state, fp_bits=cfg["fp_bits"],
                    capacity_factor=cfg["capacity_factor"], tracer=tracer)
    return pump, placed, devs


def _attempted(lanes, kind) -> int:
    """Lanes of ``kind`` that reached their owner shard."""
    return (lanes["offered", kind] + lanes["resubmitted", kind]
            - lanes["deferred", kind])


def run(ctx) -> harness.Outcome:
    log, cfg, mix, seed = ctx.log, ctx.cell.config, ctx.cell.mix, ctx.seed
    pump, placed, devs = build(ctx, log)
    ref = reference.Reference(placed)
    per = int(mix["keys_per_call"])

    def serve(kind, cls, idx):
        c = pump.call(kind, traffic.keys_of(seed, cls, idx))
        pump.run_until_drained()
        return c.results

    # Warm-up: the window's programs, twice: the second round runs on the
    # state the first returned, as every call of the window does.
    for r in range(2):
        warm = np.arange(r * per, (r + 1) * per, dtype=np.int64)
        cls = np.full(per, K.WARM, np.uint8)
        ref.insert(2 * r - 4, cls, warm, serve("insert", cls, warm))
        ref.delete(2 * r - 3, cls, warm, serve("delete", cls, warm))
        serve("lookup", np.full(per, K.ABSENT, np.uint8), WARM_BASE + warm)

    gen = closedloop.ClosedLoop(mix, seed, placed)
    calls, lat = [], []
    lanes0 = collections.Counter(pump.stats.lanes)
    most = sum(mix["cycle"].values()) if ctx.trace else float("inf")
    setup_s = time.perf_counter() - ctx.t_start
    log(f"window opens at {setup_s:.3f} s")
    with ctx.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds and len(calls) < most:
            with ctx.span("next_call"):
                kind, cls, idx = gen.next()
                keys = traffic.keys_of(seed, cls, idx)
            t = time.perf_counter()
            c = pump.call(kind, keys)
            pump.run_until_drained()
            if c.results is not None:
                np.asarray(c.results)
                lat.append(time.perf_counter() - t)
            else:
                lat.append(np.nan)
            calls.append((kind, cls, idx, c.results))
        window_s = time.perf_counter() - t0
    lanes = pump.stats.lanes - lanes0
    peak = harness.memory_peak(devs)

    n = len(calls)
    lat = np.asarray(lat)
    kinds = np.array([k for k, *_ in calls])
    answered = np.isfinite(lat)
    failed = 0
    for j, (kind, cls, idx, res) in enumerate(calls):
        if res is None:
            continue
        getattr(ref, kind)(j, cls, idx, res)
        failed += int(kind != "lookup" and not res.all())

    def lookup_all(cls, idx, pad_base):
        """Answers of ``(cls, idx)`` in calls of ``per`` keys, the last one
        filled up with absent keys from ``pad_base``."""
        fill = (-idx.size) % per
        cls = np.concatenate([cls, np.full(fill, K.ABSENT, np.uint8)])
        idx = np.concatenate([idx, pad_base + np.arange(fill)])
        out = [serve("lookup", cls[s:s + per], idx[s:s + per])
               for s in range(0, idx.size, per)]
        return (np.concatenate(out) if out else np.zeros(0, bool))[
            :idx.size - fill]

    # Read back every window insert still live, then look up a seeded sample
    # of live set-up members, absent keys and keys the window deleted,
    # shuffled together.
    m = int(cfg["check_sample"])
    acked = ref.acked(K.FRESH)
    lost = int((~lookup_all(np.full(acked.size, K.FRESH, np.uint8), acked,
                            CHECK_BASE + 2 * m)).sum())
    rng = traffic.rng_for(seed, 9)
    gone = ref.deleted(K.MEMBER)
    gone = rng.choice(gone, min(m, gone.size), replace=False)
    s_cls = np.concatenate([np.full(m, K.MEMBER, np.uint8),
                            np.full(m, K.ABSENT, np.uint8),
                            np.full(gone.size, K.MEMBER, np.uint8)])
    s_idx = np.concatenate([rng.integers(gen.member_pos, placed.size, m),
                            CHECK_BASE + np.arange(m), gone])
    order = rng.permutation(s_idx.size)
    s_cls, s_idx = s_cls[order], s_idx[order]
    ref.lookup(n, s_cls, s_idx,
               lookup_all(s_cls, s_idx, CHECK_BASE + 3 * m))

    import jax.numpy as jnp
    st = pump.state
    table_held = np.asarray(jnp.count_nonzero(st.tables, axis=(1, 2)))
    stash_held = np.asarray(jnp.count_nonzero(st.stashes[:, 0, :], axis=1))
    held = int(table_held.astype(np.int64).sum() + stash_held.sum())
    expect = (int(placed.sum()) - int(placed[ref.deleted(K.MEMBER)].sum())
              + ref.acked(K.FRESH).size + ref.acked(K.WARM).size)
    v = ref.verdict()
    lim = cfg["limits"]
    lat_ms = 1e3 * lat[answered]
    by_kind = {f"lat_p50_ms.{k}": openloop.percentile(
        1e3 * lat[answered & (kinds == k)], 50)
        for k in ("insert", "delete", "lookup")
        if (answered & (kinds == k)).any()}
    return harness.Outcome(
        metrics={"lat_p50_ms": openloop.percentile(lat_ms, 50),
                 "setup_s": setup_s},
        checks={"unanswered": (int((~answered).sum()), lim["unanswered"]),
                "false_negatives": (v["false_negatives"],
                                    lim["false_negatives"]),
                "fpr": (v["fpr"], lim["fpr"]),
                "lost_writes": (lost, lim["lost_writes"]),
                "occupancy_gap": (abs(held - expect), lim["occupancy_gap"]),
                "delete_misses": (v["delete_misses"], lim["delete_misses"]),
                "blind_deletes": (v["blind_deletes"], lim["blind_deletes"])},
        attempted=n, failed=failed, devices=devs, memory_peak_bytes=peak,
        info={"calls": n, **by_kind,
              "lat_p99_ms": openloop.percentile(lat_ms, 99),
              "lat_max_ms": float(lat_ms.max()) if lat_ms.size else None,
              "keys_per_s": n * per / window_s, "window_s": window_s,
              "compiles_in_window": ctx.compiles.names,
              "peak_bytes_per_chip": [
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs],
              "table_held_per_shard": table_held.tolist(),
              "stash_held_per_shard": stash_held.tolist(),
              "routing_lanes_in_window": {f"{w}.{k}": c for (w, k), c
                                          in sorted(lanes.items())},
              "false_positives": v["false_positives"],
              "non_member_lookups": v["non_member_lookups"],
              "lookups": v["lookups"], "held": held, "expected": expect},
        counters={"offered": sum(c for (w, _k), c in lanes.items()
                                 if w == "offered"),
                  "deferred": sum(c for (w, _k), c in lanes.items()
                                  if w == "deferred"),
                  "insert_keys": _attempted(lanes, "insert"),
                  "delete_keys": _attempted(lanes, "delete"),
                  "n_shards": cfg["n_shards"],
                  "bucket_size": cfg["bucket_size"],
                  "stash_slots": cfg["stash_slots"]})
