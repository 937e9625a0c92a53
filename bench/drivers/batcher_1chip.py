"""One chip, open loop, through the program's serving entry point.

Set-up: the table loaded on the device from the seed
(``bench.placement``), an empty stash, and one ``FilterOpBatcher`` over
``FilterOps(backend="auto")`` as the program's control planes build it
(conflict-aware scheduling and buffer donation on); a lookup and an insert
wave warm up the window's programs.  The window submits each request of the
mix when due; each request is one wave (at most ``wave_slots`` keys), and
the harness stamps it done when its answers are on the host.

After the window, through the same batcher and its same programs: every
acknowledged insert is read back, and a sample drawn from the seed of
``check_sample`` set-up members and as many absent keys is looked up, in
full waves.  The table and stash occupancy is counted, and every answer of
the window and of the read-back is held to the reference.
"""
from __future__ import annotations

import time

import numpy as np

from bench import harness, openloop, placement, reference, traffic
from bench import keys as K

WARM_BASE = 1 << 31        # absent-class indices of the warm-up probes
CHECK_BASE = 1 << 30       # absent-class indices of the read-back sample


def build(ctx, log):
    import jax.numpy as jnp

    from repro.core import filter as jfilter
    from repro.core.filter_ops import FilterOps
    from repro.kernels import ops as kops
    from repro.serving.scheduler import FilterOpBatcher
    cfg = ctx.cell.config
    dev = harness.devices_for(1, ctx.require_tpu)[0]
    t = time.perf_counter()
    table, placed = placement.load_table(
        cfg["n_buckets"], cfg["bucket_size"], seed=ctx.seed, load=cfg["load"],
        chunk=cfg["setup_chunk"], fp_bits=cfg["fp_bits"], device=dev)
    n_placed = int(placed.sum())
    log(f"set-up: {n_placed} of {placed.size} offered keys placed "
        f"(load {n_placed / (cfg['n_buckets'] * cfg['bucket_size']):.5f}) "
        f"in {time.perf_counter() - t:.3f} s, at "
        f"{time.perf_counter() - ctx.t_start:.3f} s")
    state = jfilter.FilterState(table, jnp.asarray(n_placed, jnp.int32),
                                jnp.asarray(cfg["n_buckets"], jnp.int32))
    ops = FilterOps(fp_bits=cfg["fp_bits"], backend="auto", schedule=True,
                    donate=True)
    tracer = None
    if ctx.trace:
        from repro.obs.trace import TraceRecorder
        tracer = TraceRecorder(jax_profiler=True)
    batcher = FilterOpBatcher(ops, state, stash=kops.make_stash(
        cfg["stash_slots"]), tracer=tracer)
    return batcher, placed, dev, kops


def _lookup_all(batcher, keys, slots):
    """Answers of ``keys`` looked up in full waves of the batcher."""
    out = []
    for s in range(0, keys.size, slots):
        w = batcher.submit("lookup", keys[s:s + slots])
        batcher.flush()
        out.append(np.asarray(w.results, bool))
    return np.concatenate(out) if out else np.zeros(0, bool)


def run(ctx) -> harness.Outcome:
    log, cfg, mix, seed = ctx.log, ctx.cell.config, ctx.cell.mix, ctx.seed
    batcher, placed, dev, kops = build(ctx, log)
    ref = reference.Reference(placed)
    slots = batcher.wave_slots
    if mix["keys_per_request"]["max"] > slots:
        raise ValueError("a request must fit one wave")

    # Warm-up: the window's programs.  Twice: the second round runs on the
    # state the first one returned, as every wave of the window does.
    for r in range(2):
        warm = np.arange(r * slots, (r + 1) * slots, dtype=np.int64)
        w_ins = batcher.submit("insert", K.keys_np(seed, K.WARM, warm))
        batcher.submit("lookup", K.keys_np(seed, K.ABSENT, WARM_BASE + warm))
        batcher.flush()
        ref.insert(-1, np.full(slots, K.WARM, np.uint8), warm, w_ins.results)

    sched = traffic.open_loop(mix, seed, ctx.seconds, placed)
    flat = traffic.keys_of(seed, np.concatenate(sched.cls),
                           np.concatenate(sched.idx))
    reqs = np.split(flat, np.cumsum([i.size for i in sched.idx])[:-1])
    waves = [None] * len(sched)
    log(f"traffic: {len(sched)} requests ready at "
        f"{time.perf_counter() - ctx.t_start:.3f} s")

    def submit(j):
        waves[j] = batcher.submit(str(sched.kind[j]), reqs[j])

    def answers(j):
        return waves[j].results

    dedup0 = batcher.stats.deduped_lanes
    setup_s = time.perf_counter() - ctx.t_start
    with ctx.window():
        t0, sent, done = openloop.run(sched.due, submit, batcher.flush,
                                      answers,
                                      wait_span=lambda: ctx.span("wait_due"))
        window_s = time.perf_counter() - t0
    deduped = batcher.stats.deduped_lanes - dedup0
    peak = harness.memory_peak([dev])

    n = len(sched)
    kinds = sched.kind
    lat = done - sched.due
    answered = np.isfinite(lat)
    for j in np.flatnonzero(answered):
        if kinds[j] == "insert":
            ref.insert(j, sched.cls[j], sched.idx[j], waves[j].results)
        else:
            ref.lookup(j, sched.cls[j], sched.idx[j], waves[j].results)

    # Read back every acknowledged insert, and look up a seeded sample of
    # set-up members and absent keys, shuffled together into full waves.
    lost = 0
    for cls in (K.FRESH, K.WARM):
        acked = ref.acked(cls)
        lost += int((~_lookup_all(batcher, K.keys_np(seed, cls, acked),
                                  slots)).sum())
    rng = traffic.rng_for(seed, 9)
    m = int(cfg["check_sample"])
    s_cls = np.concatenate([np.full(m, K.MEMBER, np.uint8),
                            np.full(m, K.ABSENT, np.uint8)])
    s_idx = np.concatenate([rng.integers(0, placed.size, m),
                            CHECK_BASE + np.arange(m)])
    order = rng.permutation(2 * m)
    s_cls, s_idx = s_cls[order], s_idx[order]
    ref.lookup(n, s_cls, s_idx,
               _lookup_all(batcher, traffic.keys_of(seed, s_cls, s_idx),
                           slots))

    import jax.numpy as jnp
    held = int(jnp.count_nonzero(batcher.state.table)) + \
        int(kops.stash_occupancy(batcher.stash))
    expect = int(placed.sum()) + ref.acked(K.FRESH).size + \
        ref.acked(K.WARM).size
    v = ref.verdict()
    ins = kinds == "insert"
    failed = sum(int((~waves[j].results).sum() > 0)
                 for j in np.flatnonzero(ins & answered))
    lim = cfg["limits"]
    lat_ms = 1e3 * lat[answered]
    return harness.Outcome(
        metrics={"lat_p50_ms": openloop.percentile(lat_ms, 50),
                 "lat_p99_ms": openloop.percentile(lat_ms, 99),
                 "setup_s": setup_s},
        checks={"unanswered": (int((~answered).sum()), lim["unanswered"]),
                "false_negatives": (v["false_negatives"],
                                    lim["false_negatives"]),
                "fpr": (v["fpr"], lim["fpr"]),
                "lost_writes": (lost, lim["lost_writes"]),
                "occupancy_gap": (abs(held - expect), lim["occupancy_gap"])},
        attempted=n, failed=failed, devices=[dev], memory_peak_bytes=peak,
        info={"requests": n, "beyond_p99": openloop.beyond(n, 99),
              "lat_p95_ms": openloop.percentile(lat_ms, 95),
              "lat_max_ms": float(lat_ms.max()),
              "trend": openloop.trend(lat_ms),
              "window_s": window_s,
              "late_p99_ms": 1e3 * openloop.percentile(sent - sched.due, 99),
              "late_max_ms": 1e3 * float((sent - sched.due).max()),
              "compiles_in_window": ctx.compiles.names,
              "stash_fill": int(kops.stash_occupancy(batcher.stash)),
              "false_positives": v["false_positives"],
              "non_member_lookups": v["non_member_lookups"],
              "lookups": v["lookups"], "held": held, "expected": expect,
              "deduped_lanes": deduped},
        counters={"lookup_waves": int((~ins).sum()),
                  "probe_keys": sum(waves[j].n for j in np.flatnonzero(~ins))
                  - deduped,
                  "bucket_size": cfg["bucket_size"],
                  "stash_slots": cfg["stash_slots"]})
