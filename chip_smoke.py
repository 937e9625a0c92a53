#!/usr/bin/env python3
"""Chip smoke: the served filter path end to end on one TPU.

    python chip_smoke.py              # one chip: load, serve, check
    python chip_smoke.py --chips 4    # four chips: the routed shard_map path

One chip: a 512 MiB cuckoo table (2^25 buckets x 4 slots, 16-bit
fingerprints; ``--buckets-log2 26`` for 1 GiB) plus an overflow stash is
loaded to the OCF's default 0.85 load through ``FilterOps(backend="auto",
schedule=True, donate=True).insert_spill`` in 65,536-key batches.  A ``FilterOpBatcher``
with default settings then replays the uniform, zipfian and delete_heavy
scenarios of ``serving.workloads`` against it.  Answers are checked against
exact numpy key sets: zero false negatives on every acknowledged key still
present, a false-positive rate on absent keys within 4 x 2b / 2^f (the
bound ``scripts/bench_gate.py`` applies), and table plus stash occupancy
equal to acknowledged inserts minus acknowledged deletes.

Four chips: routed inserts, deletes and lookups through the served entry
point of a sharded filter (``serving.scheduler.DeferredWritePump``) over a
4-shard state (2^24 buckets per shard, each with a stash) placed one shard
per device and loaded to the same 0.85, against the same exact reference,
with per-shard occupancy matched to the keys each shard owns.  The pump
replays deferred lanes until none remain.

Without a TPU the script exits non-zero and prints no verdict.  The last
stdout line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The phase functions take their sizes as arguments, so
``tests/test_chip_smoke.py`` rehearses them on CPU at small sizes.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import filter as jfilter  # noqa: E402
from repro.core import hashing  # noqa: E402
from repro.core.filter_ops import FilterOps  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serving.scheduler import FilterOpBatcher  # noqa: E402
from repro.serving.workloads import scenario_stream  # noqa: E402

FP_BITS = 16
BUCKET_SIZE = 4
TARGET_LOAD = 0.85            # the OCF's default o_max
LOAD_BATCH = 65536
STASH_SLOTS = 1024
SCENARIOS = ("uniform", "zipfian", "delete_heavy")
SWEEP_BATCH = 1 << 20         # lookup batch of the check sweeps
SHARD_BUCKETS = 1 << 24       # four chips: 256 MiB per shard
# Routed-write capacity per (source, owner) pair, in fair shares: at 1.0 the
# router defers some lanes of almost every write call, so the smoke drives
# the resubmit path as well as the shard-local writes.
ROUTE_CAPACITY = 1.0


def fpr_bound(fp_bits: int = FP_BITS, bucket_size: int = BUCKET_SIZE):
    """The false-positive ceiling the bench gate applies: 4 x 2b / 2^f."""
    return 4 * 2 * bucket_size / 2 ** fp_bits


# ------------------------------------------------------------------ keys --
#
# Loaded keys have the top bit set; the serving scenarios draw theirs below
# 2^63, so the two never meet.  ``_mix63`` is a bijection on [0, 2^63), so
# distinct indices give distinct keys, and the absent keys (indices from
# 2^39 up) and lookup padding (from 2^38) are disjoint from every loaded
# key.

_TOP = np.uint64(1 << 63)
_M63 = np.uint64((1 << 63) - 1)
_ABSENT_BASE = 1 << 39
_PAD_BASE = 1 << 38


def _mix63(x: np.ndarray) -> np.ndarray:
    x = x & _M63
    x ^= x >> np.uint64(31)
    x = (x * np.uint64(0x7FB5D329728EA185)) & _M63
    x ^= x >> np.uint64(27)
    x = (x * np.uint64(0x81DADEF4BC2DD44D)) & _M63
    x ^= x >> np.uint64(33)
    return x


def keys_at(seed: int, idx: np.ndarray) -> np.ndarray:
    """Keys number ``idx`` of the load stream of ``seed``."""
    return _TOP | _mix63((np.uint64(seed) << np.uint64(40))
                         + idx.astype(np.uint64))


def loaded_keys(seed: int, start: int, n: int) -> np.ndarray:
    """Keys ``start .. start+n`` of the load stream of ``seed``."""
    return keys_at(seed, np.arange(start, start + n, dtype=np.uint64))


def absent_keys(seed: int, n: int) -> np.ndarray:
    """``n`` keys that no load stream of ``seed`` and no scenario holds."""
    return loaded_keys(seed, _ABSENT_BASE, n)


def _split(keys: np.ndarray):
    hi, lo = hashing.key_to_u32_pair_np(keys)
    return jnp.asarray(hi), jnp.asarray(lo)


# -------------------------------------------------------- one chip: load --


def load_phase(n_buckets: int, *, seed: int, target_load: float = TARGET_LOAD,
               batch: int = LOAD_BATCH, stash_slots: int = STASH_SLOTS,
               backend: str = "auto", log=print) -> dict:
    """Fill a fresh table to ``target_load`` through ``insert_spill``.

    Returns the ops, state, stash, the ``ok`` mask of every loaded key, and
    the timings: ``compile_s`` (the first batch, compile included) and
    ``load_s`` (all batches).
    """
    ops = FilterOps(fp_bits=FP_BITS, backend=backend, schedule=True,
                    donate=True)
    state = jfilter.make_state(n_buckets, BUCKET_SIZE)
    stash = kops.make_stash(stash_slots)
    n_keys = batch * int(target_load * n_buckets * BUCKET_SIZE // batch)
    oks = []
    t0 = time.perf_counter()
    compile_s = 0.0
    tenth = max(1, n_keys // batch // 10) * batch
    for start in range(0, n_keys, batch):
        hi, lo = _split(loaded_keys(seed, start, batch))
        state, stash, ok = ops.insert_spill(state, stash, hi, lo)
        oks.append(ok)
        if start == 0:
            jax.block_until_ready(ok)
            compile_s = time.perf_counter() - t0
        elif start % tenth == 0:
            jax.block_until_ready(ok)
            log(f"load: {start + batch} keys "
                f"({(start + batch) / (n_buckets * BUCKET_SIZE):.3f} load) "
                f"at {time.perf_counter() - t0:.1f} s")
    ok = np.asarray(jnp.concatenate(oks)) if oks else np.zeros(0, bool)
    load_s = time.perf_counter() - t0
    return {"ops": ops, "state": state, "stash": stash, "ok": ok,
            "n_keys": n_keys, "compile_s": compile_s, "load_s": load_s}


# ------------------------------------------------------- one chip: serve --


def serve_phase(ops, state, stash, *, seed: int, waves: int,
                scenarios=SCENARIOS) -> dict:
    """Replay the scenarios through one default ``FilterOpBatcher``.

    Returns the final state and stash, and every wave in submission order
    as (kind, keys, OpWave) for the check.
    """
    batcher = FilterOpBatcher(ops, state, stash=stash)
    record = []
    t0 = time.perf_counter()
    for i, name in enumerate(scenarios):
        for op in scenario_stream(name, seed + i, waves=waves):
            record.append((op.kind, op.keys, batcher.submit(op.kind,
                                                            op.keys)))
    batcher.flush()
    return {"state": batcher.state, "stash": batcher.stash,
            "record": record, "double_buffer": batcher.double_buffer,
            "serve_s": time.perf_counter() - t0}


# ------------------------------------------------------- one chip: check --


def check_phase(ops, state, stash, *, seed: int, load_ok: np.ndarray,
                record, n_absent: int, sweep: int = SWEEP_BATCH) -> dict:
    """Hold the filter to an exact reference.

    The reference replays the served waves in order: an acknowledged insert
    adds its key, an acknowledged delete removes one copy, and each lookup
    must hit every key present at that point.  Then every loaded key that
    was acknowledged is swept, ``n_absent`` absent keys measure the
    false-positive rate, and the table plus stash must hold exactly the
    acknowledged inserts minus the acknowledged deletes.
    """
    served = collections.Counter()
    fn = fp = absent = inserted = deleted = blind = 0
    for kind, keys, wave in record:
        res = np.asarray(wave.results, bool)
        if kind == "insert":
            for k in keys[res]:
                served[int(k)] += 1
            inserted += int(res.sum())
        elif kind == "delete":
            for k in keys[res]:
                if served[int(k)] > 0:
                    served[int(k)] -= 1
                else:
                    blind += 1
            deleted += int(res.sum())
        else:
            present = np.array([served[int(k)] > 0 for k in keys], bool)
            fn += int((present & ~res).sum())
            fp += int((~present & res).sum())
            absent += int((~present).sum())

    def lookup(keys):
        hi, lo = _split(keys)
        return np.asarray(ops.lookup_with_stash(state, stash, hi, lo))

    idx = np.flatnonzero(load_ok)
    for s in range(0, idx.size, sweep):
        fn += int((~lookup(keys_at(seed, idx[s:s + sweep]))).sum())
    for s in range(0, n_absent, sweep):
        hits = lookup(loaded_keys(seed, _ABSENT_BASE + s,
                                  min(sweep, n_absent - s)))
        fp += int(hits.sum())
        absent += hits.size
    table_slots = int(jnp.count_nonzero(state.table))
    stash_slots = int(kops.stash_occupancy(stash))
    expected = int(load_ok.sum()) + inserted - deleted
    return {"false_negatives": fn, "false_positives": fp,
            "absent_probes": absent, "fpr": fp / max(absent, 1),
            "fpr_bound": fpr_bound(), "blind_deletes": blind,
            "table_slots": table_slots, "stash_slots": stash_slots,
            "count": int(state.count), "expected_occupancy": expected,
            "served_inserts": inserted, "served_deletes": deleted}


def single_chip(*, n_buckets: int, seed: int, waves: int, n_absent: int,
                target_load: float = TARGET_LOAD, batch: int = LOAD_BATCH,
                stash_slots: int = STASH_SLOTS, backend: str = "auto",
                log=print) -> dict:
    """Load, serve and check one table -> the check's numbers + ``ok``."""
    ld = load_phase(n_buckets, seed=seed, target_load=target_load,
                    batch=batch, stash_slots=stash_slots, backend=backend,
                    log=log)
    ops = ld["ops"]
    log(f"backend: {ops.resolve()}")
    for op in ("insert_stash", "probe", "delete"):   # the ops served here
        log(f"form {op}: {kops.lowering(op)}")
    log(f"table: {n_buckets} buckets x {BUCKET_SIZE} slots = "
        f"{ld['state'].table.nbytes} bytes, stash {stash_slots} slots")
    log(f"load: {int(ld['ok'].sum())} of {ld['n_keys']} keys acknowledged "
        f"in {ld['load_s']:.3f} s (first batch, compile included: "
        f"{ld['compile_s']:.3f} s)")
    sv = serve_phase(ops, ld["state"], ld["stash"], seed=seed, waves=waves)
    log(f"serve: {len(sv['record'])} waves in {sv['serve_s']:.3f} s "
        f"(double_buffer={sv['double_buffer']})")
    ck = check_phase(ops, sv["state"], sv["stash"], seed=seed,
                     load_ok=ld["ok"], record=sv["record"],
                     n_absent=n_absent)
    log(f"check: false negatives {ck['false_negatives']}; "
        f"FPR {ck['fpr']:.3e} ({ck['false_positives']} of "
        f"{ck['absent_probes']}) <= bound {ck['fpr_bound']:.3e}; "
        f"occupancy table {ck['table_slots']} + stash {ck['stash_slots']} "
        f"vs expected {ck['expected_occupancy']}; "
        f"blind deletes {ck['blind_deletes']}")
    ck["ok"] = (ck["false_negatives"] == 0 and ck["blind_deletes"] == 0
                and ck["fpr"] <= ck["fpr_bound"]
                and ck["table_slots"] + ck["stash_slots"]
                == ck["expected_occupancy"]
                and ck["count"] == ck["table_slots"]
                and int(ld["ok"].sum()) == ld["n_keys"])
    return ck


# ----------------------------------------------------------- four chips --


def _check_one_shard_per_device(arr, mesh) -> None:
    shards = arr.addressable_shards
    devs = sorted(s.device.id for s in shards)
    want = sorted(d.id for d in mesh.devices.flat)
    if devs != want or any(s.data.shape[0] != 1 for s in shards):
        raise AssertionError(
            f"expected one shard per mesh device, got "
            f"{[(s.device.id, s.data.shape) for s in shards]}")


def sharded_phase(*, n_shards: int, n_buckets: int, seed: int, batch: int,
                  n_absent: int, target_load: float = TARGET_LOAD,
                  stash_slots: int = STASH_SLOTS, backend: str = "auto",
                  log=print) -> dict:
    """Routed writes and lookups over ``n_shards`` devices, checked.

    Inserts loaded keys until the shards reach ``target_load``, then
    deletes the first quarter of them, all through the served entry point
    of a sharded filter (``DeferredWritePump.call``), ``batch`` keys per
    call.  Lanes the router defers (more than ``ROUTE_CAPACITY`` fair
    shares for one owner) are parked and replayed by the pump.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed as dist
    from repro.distributed import elastic
    from repro.serving.scheduler import DeferredWritePump

    mesh = elastic.filter_mesh(n_shards)
    lanes = NamedSharding(mesh, P("data"))
    state = dist.make_sharded_state(n_shards, n_buckets, BUCKET_SIZE,
                                    stash_slots=stash_slots)
    state = state._replace(tables=jax.device_put(state.tables, lanes),
                           stashes=jax.device_put(state.stashes, lanes))
    _check_one_shard_per_device(state.tables, mesh)
    _check_one_shard_per_device(state.stashes, mesh)
    log(f"sharded: {n_shards} shards x {n_buckets} buckets on "
        f"{[d.id for d in mesh.devices.flat]}, one shard per device")
    pump = DeferredWritePump(mesh, "data", state, fp_bits=FP_BITS,
                             capacity_factor=ROUTE_CAPACITY, backend=backend)

    def serve(kind, keys):
        """``keys`` in calls of ``batch`` -> answers, every write applied."""
        calls = [pump.call(kind, keys[s:s + batch])
                 for s in range(0, keys.size, batch)]
        pump.run_until_drained()
        return (np.concatenate([c.results for c in calls]) if calls
                else np.zeros(0, bool))

    def lookup(keys):
        # Pad the last call with absent keys, so that every call has the
        # same shape, rather than key 0, which would crowd one shard.
        padded = keys_at(seed, np.arange(_PAD_BASE, _PAD_BASE
                                         + (-keys.size) % batch))
        return serve("lookup", np.concatenate([keys, padded]))[:keys.size]

    n_keys = batch * int(target_load * n_shards * n_buckets * BUCKET_SIZE
                         // batch)
    keys = loaded_keys(seed, 0, n_keys)
    t0 = time.perf_counter()
    ins_ok = serve("insert", keys)
    insert_s = time.perf_counter() - t0
    stash_occ = np.asarray(jnp.sum(pump.state.stashes[:, 0, :] != 0, axis=1))
    victims = np.flatnonzero(ins_ok[:n_keys // 4])
    del_ok = serve("delete", keys[victims])
    present = ins_ok.copy()
    present[victims[del_ok]] = False
    fn = int((~lookup(keys[present])).sum())
    fp = int(lookup(absent_keys(seed, n_absent)).sum())
    state = pump.state
    occ = np.asarray(jnp.sum(state.tables != 0, axis=(1, 2))
                     + jnp.sum(state.stashes[:, 0, :] != 0, axis=1))
    load = n_keys / (n_shards * n_buckets * BUCKET_SIZE)
    hi, lo = hashing.key_to_u32_pair_np(keys[present])
    owned = np.bincount(hashing.owner_shard_np(hi, lo, n_shards),
                        minlength=n_shards)
    out = {"n_keys": n_keys, "load": load,
           "inserts_acked": int(ins_ok.sum()),
           "deletes_acked": int(del_ok.sum()), "insert_s": insert_s,
           "stash_after_insert": stash_occ.tolist(),
           "resubmitted_lanes": pump.stats.resubmitted,
           "false_negatives": fn, "false_positives": fp,
           "fpr": fp / max(n_absent, 1), "fpr_bound": fpr_bound(),
           "lookup_overflow": pump.stats.lanes["overflowed", "lookup"],
           "shard_occupancy": occ.tolist(), "owned": owned.tolist()}
    log(f"sharded: {out['inserts_acked']} of {n_keys} inserts (load "
        f"{load:.3f}) and {out['deletes_acked']} deletes acknowledged, "
        f"{out['resubmitted_lanes']} deferred lanes resubmitted; inserts in "
        f"{insert_s:.3f} s; stash occupancy per shard after the inserts "
        f"{out['stash_after_insert']}")
    log(f"sharded check: false negatives {fn}; FPR {out['fpr']:.3e} "
        f"({fp} of {n_absent}) <= bound {out['fpr_bound']:.3e}; per-shard "
        f"occupancy {out['shard_occupancy']} vs owned {out['owned']}")
    out["ok"] = (fn == 0 and out["fpr"] <= out["fpr_bound"]
                 and out["inserts_acked"] == n_keys
                 and out["deletes_acked"] == victims.size
                 and out["shard_occupancy"] == out["owned"])
    return out


# ----------------------------------------------------------------- main --


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the routed shard_map path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    # 2^25: on a v5e a 2^26 table loads in about 7 minutes (the last tenth
    # of the load takes a third of it); 2^25 halves that.
    ap.add_argument("--buckets-log2", type=int, default=25,
                    help="one-chip table size: 2^N buckets x 4 slots")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform is "
              f"{dev.platform!r}); this smoke runs only on a TPU chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    from importlib import metadata

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}, libtpu {libtpu}")
    print(f"device: {dev.platform} {dev.device_kind}, count {len(devices)}")

    if args.chips == 4:
        res = sharded_phase(n_shards=4, n_buckets=SHARD_BUCKETS,
                            seed=args.seed, batch=1 << 18, n_absent=1 << 22)
    else:
        res = single_chip(n_buckets=1 << args.buckets_log2, seed=args.seed,
                          waves=96, n_absent=1 << 22)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    if not res["ok"]:
        print(f"chip_smoke: FAILED {json.dumps(res, default=str)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
