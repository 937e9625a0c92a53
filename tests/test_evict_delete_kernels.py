"""PR-3 kernel validation: device-side eviction rounds and the fused delete.

Covers the acceptance criteria for closing the Pallas data-plane gaps:
  * eviction-round parity vs the lax.scan path at >= 0.9 load factor;
  * lossless rollback under a near-full-table eviction storm (a failed
    insert NEVER orphans a resident fingerprint — the paper's
    false-negative-at-saturation safeguard, on device);
  * delete parity vs the jnp scan path AND the pyfilter oracle, bit for
    bit, including duplicate keys beyond the resident multiplicity;
  * empty-batch guards on both new kernels;
  * the FilterOps pallas backend never touching the scan fallback.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PyCuckooFilter, hashing
from repro.core import filter as jf
from repro.core.filter_ops import FilterOps, evict_rounds_for_load
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.delete import delete_bulk
from repro.kernels.insert import insert_bulk
from repro.kernels.probe import probe

from conftest import random_keys

pytestmark = pytest.mark.tier1


def _pair(keys):
    hi, lo = hashing.key_to_u32_pair_np(keys)
    return jnp.asarray(hi), jnp.asarray(lo)


def _probe_all(table, hi, lo, n_buckets=None):
    n = hi.shape[0]
    pad = (-n) % 256
    hit = probe(jnp.asarray(table), jnp.pad(hi, (0, pad)),
                jnp.pad(lo, (0, pad)), fp_bits=16, n_buckets=n_buckets,
                block=256, interpret=True)
    return np.asarray(hit)[:n]


# ------------------------------------------------ eviction-round inserts --


def test_evict_rounds_parity_vs_scan_high_load(rng):
    """>= 0.9 load from empty: the kernel's bounded eviction rounds place
    the same key set the sequential scan does, and every placed key is
    findable on both backends' tables.  The 64-round budget this load needs
    comes from the config curve, not an ad-hoc override."""
    n_buckets, n = 256, 920                 # 920 / 1024 slots = 0.9
    rounds = evict_rounds_for_load(0.9)
    assert rounds == 64
    keys = random_keys(rng, n)
    hi, lo = _pair(keys)
    st = jf.make_state(n_buckets, 4)
    st_j, ok_j = jf.bulk_insert_hybrid(st, hi, lo, fp_bits=16)
    t_p, ok_p = insert_bulk(st.table, hi, lo, fp_bits=16, block=n,
                            evict_rounds=rounds, interpret=True)
    assert np.asarray(ok_j).all(), "scan path must drain this workload"
    np.testing.assert_array_equal(np.asarray(ok_p), np.asarray(ok_j))
    # fingerprint conservation: exactly one slot per placed key, and every
    # placed key answers True through the probe kernel on both tables.
    assert int((np.asarray(t_p) != 0).sum()) == n
    assert _probe_all(t_p, hi, lo).all()
    assert _probe_all(st_j.table, hi, lo).all()


def test_evict_rounds_multi_block_high_load(rng):
    """Multi-block grids accumulate through the aliased table at high load;
    placements from earlier blocks are visible (and evictable) later."""
    keys = random_keys(rng, 4096)
    hi, lo = _pair(keys)
    st = jf.make_state(1152, 4)             # 4096 / 4608 slots = 0.89
    t_p, ok_p = insert_bulk(st.table, hi, lo, fp_bits=16, block=1024,
                            evict_rounds=32, interpret=True)
    ok = np.asarray(ok_p)
    assert int((np.asarray(t_p) != 0).sum()) == int(ok.sum())
    assert _probe_all(t_p, hi, lo)[ok].all()
    # the scan path places everything here; the bounded kernel must come
    # within a hair of it (chains it gives up on report False, not corrupt)
    _, ok_j = jf.bulk_insert_hybrid(st, hi, lo, fp_bits=16)
    assert ok.sum() >= int(np.asarray(ok_j).sum()) - 8


def test_eviction_storm_rollback_never_corrupts_residents(rng):
    """Near-full table + oversized burst: chains exhaust the round budget,
    roll back, and report False — no resident fingerprint is lost or
    duplicated (count conservation, bit for bit)."""
    base = random_keys(rng, 240)            # 240 / 256 slots = 0.94
    bhi, blo = _pair(base)
    st = jf.make_state(64, 4)
    st, ok_base = jf.bulk_insert(st, bhi, blo, fp_bits=16)
    placed_base = np.asarray(ok_base)
    extra = random_keys(rng, 64)
    ehi, elo = _pair(extra)
    t, ok = insert_bulk(st.table, ehi, elo, fp_bits=16, block=64,
                        evict_rounds=8, interpret=True)
    ok = np.asarray(ok)
    assert not ok.all(), "storm must overflow the round budget"
    assert _probe_all(t, bhi, blo)[placed_base].all(), \
        "rollback lost a resident fingerprint"
    assert _probe_all(t, ehi, elo)[ok].all()
    assert int((np.asarray(t) != 0).sum()) == int(placed_base.sum() + ok.sum())


def test_filter_ops_pallas_insert_no_scan_fallback(rng, monkeypatch):
    """FilterOps(backend='pallas').insert resolves the residue on-device:
    jfilter.bulk_insert must never be called (acceptance criterion)."""
    from repro.core import filter_ops as fops_mod

    def boom(*a, **kw):
        raise AssertionError("pallas insert fell back to jfilter.bulk_insert")

    monkeypatch.setattr(fops_mod.jfilter, "bulk_insert", boom)
    keys = random_keys(rng, 1800)           # 1800 / 2048 slots = 0.88
    hi, lo = _pair(keys)
    fops = FilterOps(fp_bits=16, backend="pallas")
    st, ok = fops.insert(jf.make_state(512, 4), hi, lo)
    assert np.asarray(ok).all()
    assert int(st.count) == 1800
    assert np.asarray(fops.lookup(st, hi, lo)).all()


def test_evict_rounds_respect_active_region(rng):
    """Eviction chains stay inside the ACTIVE bucket range of a larger
    pow2 buffer (the SMEM scalar governs every round, not just round 0)."""
    keys = random_keys(rng, 1120)           # 1120 / 1200 active slots = 0.93
    hi, lo = _pair(keys)
    st = jf.make_state(300, 4, buffer_buckets=512)
    t, ok = insert_bulk(st.table, hi, lo, fp_bits=16, n_buckets=st.n_buckets,
                        block=1120, evict_rounds=32, interpret=True)
    assert not np.asarray(t)[300:].any(), "fp escaped the active region"
    assert _probe_all(t, hi, lo, n_buckets=st.n_buckets)[np.asarray(ok)].all()


# --------------------------------------------------------------- deletes --


def test_delete_kernel_parity_scan_and_oracle(rng):
    """Random deletes (hits, misses, foreign keys): kernel vs scan vs
    pyfilter, table bit-for-bit."""
    keys = random_keys(rng, 1500)
    hi, lo = _pair(keys)
    st = jf.make_state(512, 4)
    st, _ = jf.bulk_insert(st, hi, lo, fp_bits=16)
    oracle = PyCuckooFilter(n_buckets=512, bucket_size=4, fp_bits=16)
    oracle.bulk_insert(keys)
    dels = np.concatenate([keys[400:900], random_keys(rng, 300)])
    dhi, dlo = _pair(dels)
    st_j, ok_j = jf.bulk_delete(st, dhi, dlo, fp_bits=16)
    ok_o = oracle.bulk_delete(dels)
    t_p, ok_p = delete_bulk(st.table, dhi, dlo, fp_bits=16, block=800,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(ok_j), ok_o)
    np.testing.assert_array_equal(np.asarray(ok_p), ok_o)
    np.testing.assert_array_equal(np.asarray(t_p), oracle.table)
    np.testing.assert_array_equal(np.asarray(t_p), np.asarray(st_j.table))


def test_delete_duplicate_keys_parity(rng):
    """The k-th duplicate of a key clears the k-th resident copy; deletes
    beyond the multiplicity report False — matching the sequential scan and
    the oracle bit-for-bit even when duplicates share one kernel block."""
    uniq = random_keys(rng, 600)
    dups = uniq[:80]
    ins = np.concatenate([uniq, dups])      # dups resident twice
    ihi, ilo = _pair(ins)
    st = jf.make_state(512, 4)
    st, _ = jf.bulk_insert(st, ihi, ilo, fp_bits=16)
    oracle = PyCuckooFilter(n_buckets=512, bucket_size=4, fp_bits=16)
    oracle.bulk_insert(ins)
    # delete each dup three times (one more than resident), in one block
    dels = np.concatenate([dups, uniq[300:400], dups, dups])
    dhi, dlo = _pair(dels)
    st_j, ok_j = jf.bulk_delete(st, dhi, dlo, fp_bits=16)
    ok_o = oracle.bulk_delete(dels)
    t_p, ok_p = delete_bulk(st.table, dhi, dlo, fp_bits=16,
                            block=dels.size, interpret=True)
    np.testing.assert_array_equal(np.asarray(ok_j), ok_o)
    np.testing.assert_array_equal(np.asarray(ok_p), ok_o)
    np.testing.assert_array_equal(np.asarray(t_p), oracle.table)
    # third round of dup deletes must have failed (multiplicity exhausted)
    assert not np.asarray(ok_p)[-dups.size:].any()


def test_delete_buffered_active_region(rng):
    """Delete with active < buffer reads the same SMEM-scalar state."""
    keys = random_keys(rng, 800)
    hi, lo = _pair(keys)
    st = jf.make_state(300, 4, buffer_buckets=512)
    st, ok = jf.bulk_insert(st, hi, lo, fp_bits=16)
    st_j, ok_j = jf.bulk_delete(st, hi, lo, fp_bits=16)
    t_p, ok_p = delete_bulk(st.table, hi, lo, fp_bits=16,
                            n_buckets=st.n_buckets, block=800, interpret=True)
    np.testing.assert_array_equal(np.asarray(ok_p), np.asarray(ok_j))
    np.testing.assert_array_equal(np.asarray(t_p), np.asarray(st_j.table))


def test_filter_ops_delete_dispatch_and_count(rng, monkeypatch):
    """FilterOps(backend='pallas').delete dispatches to the delete kernel
    (not the scan) and keeps the live count in sync."""
    calls = {"delete": 0}
    real = kops.delete_bulk

    def spy(*a, **kw):
        calls["delete"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(kops, "delete_bulk", spy)
    keys = random_keys(rng, 1000)
    hi, lo = _pair(keys)
    fops = FilterOps(fp_bits=16, backend="pallas")
    st, ok = fops.insert(jf.make_state(512, 4), hi, lo)
    st2, okd = fops.delete(st, hi[:400], lo[:400])
    assert calls["delete"] == 1
    assert np.asarray(okd).all()
    assert int(st2.count) == int(st.count) - 400
    st_j, okd_j = FilterOps(fp_bits=16, backend="jnp").delete(
        st, hi[:400], lo[:400])
    np.testing.assert_array_equal(np.asarray(okd), np.asarray(okd_j))
    np.testing.assert_array_equal(np.asarray(st2.table),
                                  np.asarray(st_j.table))


@pytest.fixture(scope="module")
def verified_batch():
    """A near-full table with a stash, and one batch of verified deletes of
    everything it holds: keys resident twice deleted twice, keys the
    table had no room for parked in the stash, and ``valid=False`` lanes.
    Laid out as a routed delete hands a shard its lanes: two rows of 4,096,
    each its keys (with padding lanes among them) and then padding."""
    rng = np.random.RandomState(7)
    uniq = random_keys(rng, 3700)
    ins = np.concatenate([uniq, uniq[:200]])
    hi, lo = _pair(ins)
    st, placed = jf.bulk_insert(jf.make_state(1024, 4), hi, lo, fp_bits=16,
                                max_disp=16)
    stash, spilled = kops.stash_spill_ref(kops.make_stash(512), hi, lo,
                                          ~placed, fp_bits=16,
                                          n_buckets=1024)
    assert (~np.asarray(placed)).sum() > 20
    assert np.asarray(placed | spilled).all()
    keys = random_keys(rng, 8192)
    valid = np.zeros(8192, bool)
    for row, dels in enumerate(np.array_split(ins[rng.permutation(ins.size)],
                                              2)):
        lanes = np.sort(rng.choice(dels.size + 250, dels.size,
                                   replace=False)) + 4096 * row
        keys[lanes], valid[lanes] = dels, True
    dhi, dlo = _pair(keys)
    return st.table, stash, dhi, dlo, jnp.asarray(valid)


def _delete_at(batch, block, monkeypatch):
    """(ok, table) of the emulated ``delete_bulk`` and (ok, table, stash) of
    ``filter_delete`` with the stash, both at ``block`` (None: one step)."""
    table, stash, hi, lo, valid = batch
    n = hi.shape[0]
    t, ok = delete_bulk(table, hi, lo, fp_bits=16, valid=valid,
                        block=block or n, interpret=False, emulate=True)
    # filter_delete takes its block from ``delete_block``, pinned here.
    monkeypatch.setattr(kops, "delete_block",
                        lambda n_keys, table_bytes: block or n)
    out = kops.filter_delete(table, hi, lo, fp_bits=16, valid=valid,
                             stash=stash, use_pallas="always")
    monkeypatch.undo()
    return [np.asarray(x) for x in (ok, t, *out)]


@pytest.mark.parametrize("block", [128, 1024, 4096, None])
def test_verified_delete_outcome_independent_of_block(verified_batch, block,
                                                      monkeypatch):
    """Emulated deletes of one verified batch with duplicates, stash-parked
    keys and padding lanes: answers, table and stash are bit for bit those
    of block 128, which are those of the sequential scan."""
    got = _delete_at(verified_batch, block, monkeypatch)
    base = _delete_at(verified_batch, 128, monkeypatch)
    for g, b in zip(got, base):
        np.testing.assert_array_equal(g, b)
    table, stash, hi, lo, valid = verified_batch
    t_r, s_r, ok_r = kops.filter_delete(table, hi, lo, fp_bits=16,
                                        valid=valid, stash=stash,
                                        use_pallas="never")
    ok, _t, t_f, s_f, ok_f = got
    np.testing.assert_array_equal(ok_f, np.asarray(ok_r))
    np.testing.assert_array_equal(t_f, np.asarray(t_r))
    np.testing.assert_array_equal(s_f, np.asarray(s_r))
    assert ok_f[np.asarray(valid)].all() and not ok_f[~np.asarray(valid)].any()
    assert not t_f.any() and not s_f[0].any()     # everything deleted
    assert not ok[~np.asarray(valid)].any()


# ---------------------------------------------------------------- guards --


def test_empty_batch_guards_new_kernels(rng):
    """Zero-length batches return empty results through every entry point
    of both new kernels — no ZeroDivisionError in block-size math."""
    st = jf.make_state(256, 4)
    e = jnp.zeros((0,), jnp.uint32)
    t, ok = kops.filter_insert(st.table, e, e, fp_bits=16, evict_rounds=16,
                               use_pallas="always")
    assert np.asarray(ok).shape == (0,) and not np.asarray(t).any()
    t, ok = kops.filter_delete(st.table, e, e, fp_bits=16,
                               use_pallas="always")
    assert np.asarray(ok).shape == (0,) and not np.asarray(t).any()
    for backend in ("jnp", "pallas"):
        fops = FilterOps(fp_bits=16, backend=backend)
        st2, ok = fops.delete(st, e, e)
        assert np.asarray(ok).shape == (0,) and int(st2.count) == 0


def test_delete_ref_fallback_matches_kernel(rng):
    """ops.filter_delete's non-kernel arm (the scan oracle) agrees with the
    kernel arm on a random workload — 'auto' dispatch can't change answers."""
    keys = random_keys(rng, 1200)
    hi, lo = _pair(keys)
    st = jf.make_state(512, 4)
    st, _ = jf.bulk_insert(st, hi, lo, fp_bits=16)
    dels = np.concatenate([keys[:500], random_keys(rng, 200)])
    dhi, dlo = _pair(dels)
    t_k, ok_k = kops.filter_delete(st.table, dhi, dlo, fp_bits=16,
                                   use_pallas="always")
    t_r, ok_r = kops.filter_delete(st.table, dhi, dlo, fp_bits=16,
                                   use_pallas="never")
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_r))
    np.testing.assert_array_equal(np.asarray(t_k), np.asarray(t_r))


def test_insert_ref_fallback_completes_residue(rng):
    """ops.filter_insert with evict_rounds>0 on the non-kernel arm finishes
    the whole insert too (optimistic round + scan residue)."""
    keys = random_keys(rng, 1800)           # 0.88 load
    hi, lo = _pair(keys)
    st = jf.make_state(512, 4)
    t_r, ok_r = kops.filter_insert(st.table, hi, lo, fp_bits=16,
                                   evict_rounds=32, use_pallas="never")
    assert np.asarray(ok_r).all()
    t_k, ok_k = kops.filter_insert(st.table, hi, lo, fp_bits=16,
                                   evict_rounds=32, use_pallas="always")
    assert np.asarray(ok_k).all()
    assert _probe_all(t_k, hi, lo).all() and _probe_all(t_r, hi, lo).all()


# ----------------------------------------------- consumers of the kernels --


def test_distributed_shard_delete_roundtrip(rng):
    """local_shard_delete_host deletes through FilterOps on the owner shard
    only, on both backends."""
    from repro.core import distributed as dist
    keys = random_keys(rng, 1024)
    hi, lo = _pair(keys)
    st = jf.make_state(512, 4)
    st, _ = jf.bulk_insert(st, hi, lo, fp_bits=16)
    for backend in ("jnp", "pallas"):
        sh = dist.ShardedFilterState(
            tables=jnp.stack([st.table, st.table]))
        sh2, ok = dist.local_shard_delete_host(sh, 0, hi[:200], lo[:200],
                                               fp_bits=16, backend=backend)
        assert np.asarray(ok).all()
        # shard 1 untouched, shard 0 lost exactly 200 fingerprints
        np.testing.assert_array_equal(np.asarray(sh2.tables[1]),
                                      np.asarray(st.table))
        assert int((np.asarray(sh2.tables[0]) != 0).sum()) == \
            int((np.asarray(st.table) != 0).sum()) - 200


def test_kvcache_evict_reaches_delete_kernel(rng, monkeypatch):
    """PrefixCacheIndex(backend='pallas') eviction path runs the fused
    delete kernel end-to-end (serving-layer thread-through)."""
    from repro.serving.kvcache import PrefixCacheIndex
    calls = {"delete": 0}
    real = kops.delete_bulk

    def spy(*a, **kw):
        calls["delete"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(kops, "delete_bulk", spy)
    idx = PrefixCacheIndex(backend="pallas", block=32)
    tokens = rng.randint(0, 1000, size=256).astype(np.uint32)
    idx.admit(tokens)
    assert idx.match_prefix(tokens) == 256 // 32
    assert idx.evict(tokens) == 256 // 32
    assert calls["delete"] > 0, "evict did not reach the delete kernel"
    assert idx.match_prefix(tokens) == 0
