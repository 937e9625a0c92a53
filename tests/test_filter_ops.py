"""FilterOps backend dispatch: cross-backend parity, kernel routing, the
ops.py precedence regression, and the vectorized keystore."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OCF, OcfConfig, PyCuckooFilter, hashing
from repro.core import filter as jf
from repro.core.filter_ops import FilterOps
from repro.core.keystore import VectorKeystore
from repro.kernels import ops as kops

from conftest import random_keys

pytestmark = pytest.mark.tier1


def _pair(keys):
    hi, lo = hashing.key_to_u32_pair_np(keys)
    return jnp.asarray(hi), jnp.asarray(lo)


# ------------------------------------------------------- backend parity ---


def test_lookup_parity_jnp_pallas_pyfilter(rng):
    """Same table, same probes: jnp, pallas (interpret), and the pyfilter
    oracle must agree bit-for-bit — including the false-positive bits."""
    keys = random_keys(rng, 1500)
    probes = np.concatenate([keys, random_keys(rng, 20000)])
    oracle = PyCuckooFilter(n_buckets=1024, bucket_size=4, fp_bits=16)
    oracle.bulk_insert(keys)
    st = jf.make_state(1024, 4)
    hi, lo = _pair(keys)
    st, _ = jf.bulk_insert(st, hi, lo, fp_bits=16)  # table-exact vs oracle
    phi, plo = _pair(probes)
    want = oracle.bulk_lookup(probes)
    got_jnp = np.asarray(FilterOps(fp_bits=16, backend="jnp").lookup(
        st, phi, plo))
    got_pl = np.asarray(FilterOps(fp_bits=16, backend="pallas").lookup(
        st, phi, plo))
    np.testing.assert_array_equal(want, got_jnp)
    np.testing.assert_array_equal(want, got_pl)


def test_lookup_parity_buffered_state(rng):
    """Active capacity < pow2 buffer: both backends read the same dynamic
    state (the pallas kernel takes the active count as an SMEM scalar)."""
    keys = random_keys(rng, 900)
    hi, lo = _pair(keys)
    st = jf.make_state(300, 4, buffer_buckets=512)
    st, ok = jf.bulk_insert(st, hi, lo, fp_bits=16)
    probes = np.concatenate([keys, random_keys(rng, 5000)])
    phi, plo = _pair(probes)
    a = np.asarray(FilterOps(fp_bits=16, backend="jnp").lookup(st, phi, plo))
    b = np.asarray(FilterOps(fp_bits=16, backend="pallas").lookup(st, phi, plo))
    np.testing.assert_array_equal(a, b)
    assert a[:900][np.asarray(ok)].all()


def test_insert_parity_single_block(rng):
    """For a single kernel block the pallas optimistic round reproduces the
    jnp round table-for-table, so the full hybrid insert is identical."""
    keys = random_keys(rng, 1000)
    hi, lo = _pair(keys)
    st_j, ok_j = FilterOps(fp_bits=16, backend="jnp").insert(
        jf.make_state(512, 4), hi, lo)
    st_p, ok_p = FilterOps(fp_bits=16, backend="pallas").insert(
        jf.make_state(512, 4), hi, lo)
    np.testing.assert_array_equal(np.asarray(st_j.table),
                                  np.asarray(st_p.table))
    np.testing.assert_array_equal(np.asarray(ok_j), np.asarray(ok_p))
    assert int(st_j.count) == int(st_p.count)
    # and membership agrees with the oracle for every key both inserted
    oracle = PyCuckooFilter(n_buckets=512, bucket_size=4, fp_bits=16)
    ok_o = oracle.bulk_insert(keys)
    both = np.asarray(ok_j) & ok_o
    hits_p = np.asarray(FilterOps(fp_bits=16, backend="pallas").lookup(
        st_p, hi, lo))
    assert hits_p[both].all() and oracle.bulk_lookup(keys)[both].all()


def test_insert_parity_multi_chunk_membership(rng):
    """Across kernel blocks layouts may differ (blocks see earlier blocks'
    placements) but membership answers for inserted keys never do."""
    keys = random_keys(rng, 5000)
    hi, lo = _pair(keys)
    st_j, ok_j = FilterOps(fp_bits=16, backend="jnp").insert(
        jf.make_state(4096, 4), hi, lo)
    st_p, ok_p = FilterOps(fp_bits=16, backend="pallas").insert(
        jf.make_state(4096, 4), hi, lo)
    assert np.asarray(ok_j).all() and np.asarray(ok_p).all()
    assert int(st_j.count) == int(st_p.count) == 5000
    for ops_, st in ((FilterOps(fp_bits=16, backend="jnp"), st_j),
                     (FilterOps(fp_bits=16, backend="pallas"), st_p)):
        assert np.asarray(ops_.lookup(st, hi, lo)).all()


def test_probe_table_backend_parity(rng):
    """Raw-table probe (the distributed shard path) agrees across backends."""
    keys = random_keys(rng, 2000)
    hi, lo = _pair(keys)
    st = jf.make_state(1024, 4)
    st, _ = jf.bulk_insert(st, hi, lo, fp_bits=16)
    probes = np.concatenate([keys, random_keys(rng, 4000)])
    phi, plo = _pair(probes)
    a = np.asarray(FilterOps(fp_bits=16, backend="jnp").probe_table(
        st.table, phi, plo))
    b = np.asarray(FilterOps(fp_bits=16, backend="pallas").probe_table(
        st.table, phi, plo))
    np.testing.assert_array_equal(a, b)
    assert a[:2000].all()


# ------------------------------------------------------ kernel routing ----


def test_ocf_pallas_backend_dispatches_through_kernels(rng, monkeypatch):
    """OCF(backend='pallas') must reach the Pallas kernels for the probe,
    the full insert (incl. eviction rounds), and the delete — with NO
    lax.scan fallback anywhere on the path (acceptance criterion)."""
    calls = {"probe": 0, "insert": 0, "delete": 0, "scan_fallback": 0}
    real_probe, real_insert = kops.probe, kops.insert_bulk
    real_delete = kops.delete_bulk

    real_probe_emulated = kops.probe_emulated

    def probe_spy(*a, **kw):
        calls["probe"] += 1
        return real_probe(*a, **kw)

    def probe_emulated_spy(*a, **kw):
        # the off-TPU fast path FilterOps.lookup takes (same kernel body,
        # XLA-compiled — see kernels/probe.py::probe_emulated)
        calls["probe"] += 1
        return real_probe_emulated(*a, **kw)

    def insert_spy(*a, **kw):
        calls["insert"] += 1
        return real_insert(*a, **kw)

    def delete_spy(*a, **kw):
        calls["delete"] += 1
        return real_delete(*a, **kw)

    def scan_spy(*a, **kw):
        calls["scan_fallback"] += 1
        raise AssertionError("pallas backend fell back to the scan path")

    monkeypatch.setattr(kops, "probe", probe_spy)
    monkeypatch.setattr(kops, "probe_emulated", probe_emulated_spy)
    monkeypatch.setattr(kops, "insert_bulk", insert_spy)
    monkeypatch.setattr(kops, "delete_bulk", delete_spy)
    from repro.core import filter_ops as fops_mod
    monkeypatch.setattr(fops_mod.jfilter, "bulk_insert", scan_spy)
    monkeypatch.setattr(fops_mod.jfilter, "bulk_delete", scan_spy)
    ocf = OCF(OcfConfig(capacity=4096, backend="pallas"))
    keys = random_keys(rng, 1000)
    ocf.insert(keys)
    assert calls["insert"] > 0, "insert did not go through the Pallas kernel"
    hits = ocf.lookup(keys)
    assert calls["probe"] > 0, "lookup did not go through the Pallas kernel"
    assert hits.all()
    ocf.delete(keys[:300])
    assert calls["delete"] > 0, "delete did not go through the Pallas kernel"
    assert calls["scan_fallback"] == 0
    assert ocf.lookup(keys[300:]).all(), "delete disturbed a resident key"
    monkeypatch.undo()  # un-patch the scan path before the jnp comparison
    # same answers as the jnp backend end-to-end
    ocf_j = OCF(OcfConfig(capacity=4096, backend="jnp"))
    ocf_j.insert(keys)
    ocf_j.delete(keys[:300])
    assert ocf_j.lookup(keys[300:]).all()
    assert ocf.count == ocf_j.count


def test_use_pallas_always_never_demoted(rng, monkeypatch):
    """Regression for the seed precedence bug: a VMEM estimate above budget
    silently demoted use_pallas='always' to the ref path."""
    calls = {"probe": 0}
    real_probe = kops.probe

    def probe_spy(*a, **kw):
        calls["probe"] += 1
        return real_probe(*a, **kw)

    monkeypatch.setattr(kops, "probe", probe_spy)
    # 1M buckets x 4 slots x 4 bytes = 16 MB > the 12 MB kernel budget
    table = jnp.zeros((1 << 20, 4), jnp.uint32)
    assert table.size * 4 > kops.VMEM_TABLE_BUDGET
    keys = random_keys(rng, 256)
    hi, lo = _pair(keys)
    kops.filter_lookup(table, hi, lo, fp_bits=16, use_pallas="auto")
    assert calls["probe"] == 0, "'auto' must respect the VMEM budget"
    kops.filter_lookup(table, hi, lo, fp_bits=16, use_pallas="never")
    assert calls["probe"] == 0
    kops.filter_lookup(table, hi, lo, fp_bits=16, use_pallas="always")
    assert calls["probe"] == 1, "'always' must never fall back to ref"


def test_bulk_insert_hybrid_is_fully_jittable(rng):
    """Regression: the seed pulled bool(jnp.any(residue)) to the host, which
    raises TracerBoolConversionError under an outer jit."""
    keys = random_keys(rng, 512)
    hi, lo = _pair(keys)

    @jax.jit
    def run(state, hi, lo):
        return jf.bulk_insert_hybrid(state, hi, lo, fp_bits=16)

    st, ok = run(jf.make_state(512, 4), hi, lo)
    assert np.asarray(ok).all()
    assert int(st.count) == 512


# ------------------------------------------------- vectorized keystore ----


def test_keystore_matches_dict_reference(rng):
    """Batch add/remove against the seed's dict-loop semantics, with
    duplicate keys inside and across batches."""
    ks = VectorKeystore()
    ref: dict[int, int] = {}
    for _ in range(20):
        batch = rng.randint(0, 50, size=rng.randint(1, 40)).astype(np.uint64)
        if rng.rand() < 0.5:
            ks.add(batch)
            for k in batch.tolist():
                ref[k] = ref.get(k, 0) + 1
        else:
            got = ks.remove(batch)
            want = np.zeros(batch.size, bool)
            for i, k in enumerate(batch.tolist()):
                if ref.get(k, 0) > 0:
                    ref[k] -= 1
                    if ref[k] == 0:
                        del ref[k]
                    want[i] = True
            np.testing.assert_array_equal(got, want)
        assert ks.total == sum(ref.values())
        assert ks.unique == len(ref)
    want_all = np.sort(np.fromiter(
        (k for k, m in ref.items() for _ in range(m)), dtype=np.uint64,
        count=sum(ref.values())))
    np.testing.assert_array_equal(np.sort(ks.materialize()), want_all)


def test_keystore_remove_per_occurrence_order(rng):
    ks = VectorKeystore()
    ks.add(np.array([7, 7], dtype=np.uint64))
    got = ks.remove(np.array([7, 7, 7], dtype=np.uint64))
    np.testing.assert_array_equal(got, [True, True, False])
    assert ks.total == 0 and ks.unique == 0


def test_ocf_duplicate_delete_semantics(rng):
    """Multiplicity survives the vectorization: the k-th delete of a key
    succeeds only while the keystore holds k copies."""
    ocf = OCF(OcfConfig(capacity=4096))
    k = random_keys(rng, 1)
    ocf.insert(np.concatenate([k, k]))
    assert len(ocf) == 2
    present = ocf.delete(np.concatenate([k, k, k]))
    np.testing.assert_array_equal(present, [True, True, False])
    assert ocf.stats.blind_deletes_blocked == 1
    assert not ocf.contains_key_exact(int(k[0]))


def test_filter_ops_rebuild_roundtrip(rng):
    keys = random_keys(rng, 3000)
    hi, lo = _pair(keys)
    fops = FilterOps(fp_bits=16, backend="jnp")
    st, ok = fops.rebuild(hi, lo, 2048, 4, buffer_buckets=4096)
    assert np.asarray(ok).all()
    assert np.asarray(fops.lookup(st, hi, lo)).all()


def test_serving_backend_threads_through(rng):
    from repro.serving.kvcache import PrefixCacheIndex
    idx = PrefixCacheIndex(backend="jnp")
    assert idx.ocf.config.backend == "jnp"
    assert idx.ocf.ops == FilterOps(fp_bits=16, max_disp=500, backend="jnp",
                                    schedule=True, donate=True)
    cfg = OcfConfig(capacity=4096, backend="auto")
    idx2 = PrefixCacheIndex(config=cfg, backend="pallas")
    assert idx2.ocf.config.backend == "pallas"
    tokens = rng.randint(0, 1000, size=256).astype(np.uint32)
    idx.admit(tokens)
    assert idx.match_prefix(tokens) == 256 // idx.block


def test_empty_batch_backend_parity(rng):
    """Zero-length batches return empty results on BOTH backends (the
    pallas path used to ZeroDivisionError in the block-size computation)."""
    st = jf.make_state(512, 4)
    e = jnp.zeros((0,), jnp.uint32)
    for backend in ("jnp", "pallas"):
        fops = FilterOps(fp_bits=16, backend=backend)
        assert np.asarray(fops.lookup(st, e, e)).shape == (0,)
        st2, ok = fops.insert(st, e, e)
        assert np.asarray(ok).shape == (0,) and int(st2.count) == 0
        assert np.asarray(fops.probe_table(st.table, e, e)).shape == (0,)


def test_distributed_replicated_backend_param(rng):
    from repro.core import distributed as dist
    keys = random_keys(rng, 1024)
    hi, lo = _pair(keys)
    st = jf.make_state(512, 4)
    st, _ = jf.bulk_insert(st, hi, lo, fp_bits=16)
    tables = jnp.stack([st.table, jnp.zeros_like(st.table)])
    hits = dist.replicated_lookup(tables, hi, lo, fp_bits=16, backend="jnp")
    assert np.asarray(hits).all()


# ------------------------------------------------------------ block rule ---

GIB2 = 2 ** 31                         # one 2^27-bucket shard's table
# Each op's BLOCK from the VMEM model: (op, table bytes, keyword arguments)
# -> block.  At 2 GiB nothing fits the VMEM budget, so every op takes 128.
VMEM_BLOCKS = [
    ("probe", GIB2, {}, 128),
    ("probe", GIB2, {"stash_slots": 1024}, 128),
    ("insert", GIB2, {"evict_rounds": 32, "stash_slots": 1024,
                      "n_keys": 16388}, 128),
    ("delete", GIB2, {"n_keys": 16388}, 128),
    ("probe", 2 ** 20, {}, 8192),
    ("probe", 4 * 2 ** 20, {"stash_slots": 1024}, 4096),
    ("insert", 2 ** 20, {"evict_rounds": 32, "n_keys": 512}, 512),
    ("insert", 2 ** 20, {"evict_rounds": 32, "stash_slots": 1024,
                         "n_keys": 16388}, 128),
    ("delete", 2 ** 20, {"n_keys": 512}, 512),
    ("delete", 2 ** 20, {"n_keys": 16388}, 128),
    ("delete", 16384, {}, 128),
]


def test_block_pins_and_delete_live_steps():
    """Every op's block is the VMEM model's; a delete's block is that one
    capped at its lanes, and its emulated grid runs only the steps that
    hold a valid lane."""
    from repro.kernels.delete import live_steps
    for op, tb, kw, block in VMEM_BLOCKS:
        assert kops.autotune_block(op, table_bytes=tb, **kw) == block
    # One shard of the four-chip cell: 4 rows of 4,097 lanes.
    b = kops.delete_block(16388, table_bytes=GIB2)
    assert b == 128
    assert kops.delete_block(5, table_bytes=GIB2) == 5
    lanes = np.pad(np.ones(16388, bool), (0, -16388 % b))
    assert int(live_steps(lanes, b).sum()) == -(-16388 // b)
    lanes[4097:] = False                  # one source row holds keys
    assert int(live_steps(lanes, b).sum()) == -(-4097 // b)
