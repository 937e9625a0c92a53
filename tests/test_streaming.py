"""Streaming subsystem validation: overflow stash, TTL generations,
admission backpressure, and the newly-unlocked sharded Pallas probe.

Covers the ISSUE-4 acceptance criteria:
  * with ``backend="pallas"`` at 0.9 load, an eviction-storm insert batch
    that previously reported failures lands EVERY key via the stash,
    parity-checked against the stash-extended pyfilter oracle;
  * single-lane chains reproduce the oracle bit for bit (table AND stash);
  * generation rotation keeps the last K batches visible, TTL expiry is
    lazy, and retirement recycles the preallocated buffer pool;
  * stash occupancy + generation fill drive admission with hysteresis;
  * ``distributed_lookup`` / ``replicated_lookup`` accept the backend flag
    and the Pallas path agrees with jnp inside ``shard_map``;
  * ``evict_rounds`` defaults derive from the configured operating load
    (0.85 -> 32, 0.9 -> 64) instead of the old flat 32;
  * empty batches are safe through every new entry point.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import filter as jf
from repro.core import hashing
from repro.core.filter_ops import FilterOps, evict_rounds_for_load
from repro.core.ocf import OCF, OcfConfig
from repro.kernels import ops as kops
from repro.kernels.insert import insert_bulk
from repro.kernels.stash import make_stash, stash_occupancy
from repro.streaming import (AdmissionConfig, AdmissionController,
                             GenerationConfig, GenerationalFilter,
                             PyStashFilter, congestion_signal)

from conftest import random_keys

pytestmark = pytest.mark.tier1


def _pair(keys):
    hi, lo = hashing.key_to_u32_pair_np(keys)
    return jnp.asarray(hi), jnp.asarray(lo)


# ------------------------------------------------------------ stash core --


def test_stash_single_lane_bit_for_bit_oracle(rng):
    """One key per kernel call == the sequential oracle's chain schedule:
    table and stash bit-for-bit, through spill AND stash-full rollback."""
    n_buckets, bs, rounds, slots = 64, 4, 8, 16
    oracle = PyStashFilter(n_buckets=n_buckets, bucket_size=bs, fp_bits=16,
                           evict_rounds=rounds, stash_slots=slots)
    table = jnp.zeros((n_buckets, bs), jnp.uint32)
    stash = make_stash(slots)
    keys = random_keys(rng, 300)
    ok_k, ok_o = [], []
    for k in keys:
        hi, lo = _pair(np.array([k], dtype=np.uint64))
        table, stash, ok = insert_bulk(table, hi, lo, fp_bits=16,
                                       evict_rounds=rounds, stash=stash,
                                       block=1, interpret=True)
        ok_k.append(bool(np.asarray(ok)[0]))
        ok_o.append(oracle.insert(int(k)))
    np.testing.assert_array_equal(np.array(ok_k), np.array(ok_o))
    np.testing.assert_array_equal(np.asarray(table), oracle.table)
    np.testing.assert_array_equal(np.asarray(stash), oracle.stash_array())
    assert oracle.spills == slots, "stash must have filled"
    assert not all(ok_k), "stash-full rollback must have been exercised"


def test_eviction_storm_lands_all_keys_via_stash(rng):
    """ISSUE-4 acceptance: the PR-3 eviction-storm workload (0.94-load
    table + oversized burst + tiny round budget) that reported failures
    without a stash now lands EVERY key, with fingerprint conservation and
    membership parity on the pallas backend."""
    base = random_keys(rng, 240)            # 240 / 256 slots = 0.94
    bhi, blo = _pair(base)
    st = jf.make_state(64, 4)
    st, ok_base = jf.bulk_insert(st, bhi, blo, fp_bits=16)
    extra = random_keys(rng, 64)
    ehi, elo = _pair(extra)
    # without a stash the storm overflows the budget (PR-3 behavior) ...
    _t0, ok0 = insert_bulk(st.table, ehi, elo, fp_bits=16, block=64,
                           evict_rounds=8, interpret=True)
    assert not np.asarray(ok0).all(), "storm must overflow without a stash"
    # ... with one, every key lands
    t, stash, ok = insert_bulk(st.table, ehi, elo, fp_bits=16, block=64,
                               evict_rounds=8, stash=make_stash(128),
                               interpret=True)
    ok = np.asarray(ok)
    assert ok.all(), "stash must absorb the whole storm"
    spilled = int(stash_occupancy(stash))
    assert spilled > 0
    placed_base = int(np.asarray(ok_base).sum())
    assert int((np.asarray(t) != 0).sum()) + spilled == placed_base + 64
    # every key (base + storm) answers True through the fused stash probe
    allhi = jnp.concatenate([bhi, ehi])
    alllo = jnp.concatenate([blo, elo])
    hit = kops.filter_lookup(t, allhi, alllo, fp_bits=16, stash=stash,
                             use_pallas="always")
    mask = np.concatenate([np.asarray(ok_base), ok])
    assert np.asarray(hit)[mask].all()


def test_storm_parity_vs_stash_oracle_membership(rng):
    """Batched storm vs the stash-extended oracle: same per-key membership
    answers and the same total state size (multi-lane schedules may place
    fingerprints differently; membership and conservation may not)."""
    keys = random_keys(rng, 920)            # 920 / 1024 slots = 0.9 load
    hi, lo = _pair(keys)
    rounds = evict_rounds_for_load(0.9)
    oracle = PyStashFilter(n_buckets=256, bucket_size=4, fp_bits=16,
                           evict_rounds=rounds, stash_slots=128)
    ok_o = np.array([oracle.insert(int(k)) for k in keys])
    table, stash, ok = insert_bulk(
        jnp.zeros((256, 4), jnp.uint32), hi, lo, fp_bits=16,
        evict_rounds=rounds, stash=make_stash(128), block=920,
        interpret=True)
    ok = np.asarray(ok)
    assert ok.all() and ok_o.all()
    assert (int((np.asarray(table) != 0).sum()) + int(stash_occupancy(stash))
            == oracle.count + len(oracle.stash))
    hit = kops.filter_lookup(table, hi, lo, fp_bits=16, stash=stash,
                             use_pallas="always")
    hit_o = np.array([oracle.lookup(int(k)) for k in keys])
    np.testing.assert_array_equal(np.asarray(hit), hit_o)


def test_stash_lookup_kernel_vs_ref_arm(rng):
    """ops.filter_lookup with a stash: the fused kernel arm and the jnp
    ref arm answer identically (dispatch can't change answers)."""
    keys = random_keys(rng, 500)
    hi, lo = _pair(keys)
    st = jf.make_state(64, 4)               # tiny: guarantees spills
    t, stash, ok = kops.filter_insert(st.table, hi, lo, fp_bits=16,
                                      evict_rounds=8, stash=make_stash(64),
                                      use_pallas="always")
    probes = np.concatenate([keys, random_keys(rng, 500)])
    phi, plo = _pair(probes)
    h_k = kops.filter_lookup(t, phi, plo, fp_bits=16, stash=stash,
                             use_pallas="always")
    h_r = kops.filter_lookup(t, phi, plo, fp_bits=16, stash=stash,
                             use_pallas="never")
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))


def test_filter_ops_insert_spill_count_and_backends(rng):
    """FilterOps.insert_spill: state.count tracks table-resident
    fingerprints only (stash counted separately), and both backends land
    the same lanes."""
    keys = random_keys(rng, 300)
    hi, lo = _pair(keys)
    for backend in ("pallas", "jnp"):
        fops = FilterOps(fp_bits=16, backend=backend, evict_rounds=8)
        st = jf.make_state(64, 4)
        st, stash, ok = fops.insert_spill(st, make_stash(64), hi, lo)
        assert np.asarray(ok).all()
        spilled = int(stash_occupancy(stash))
        assert spilled > 0, "workload must spill"
        assert int(st.count) == int((np.asarray(st.table) != 0).sum())
        assert int(st.count) + spilled == 300
        hits = fops.lookup_with_stash(st, stash, hi, lo)
        assert np.asarray(hits).all()


# --------------------------------------------------------- generations ---


def test_generation_rotation_keeps_last_k_visible(rng):
    """Explicit rotation: the ring keeps exactly the last K generations'
    keys visible and drops the one rotated past, on the pallas backend."""
    cfg = GenerationConfig(generations=3, capacity=2048, stash_slots=32,
                           backend="pallas", ttl=None)
    gf = GenerationalFilter(cfg, now=0.0)
    batches = [random_keys(rng, 700) for _ in range(4)]
    for i, b in enumerate(batches):
        assert gf.insert(b, now=float(i)).all()
        if i < len(batches) - 1:
            gf.rotate(now=float(i))     # seal this batch's generation
    assert gf.stats.rotations == 3
    assert gf.live_generations == 3
    for b in batches[-3:]:
        assert gf.lookup(b, now=10.0).all()
    # the first batch aged out of the ring (false positives only)
    assert not gf.lookup(batches[0], now=10.0).all()


def test_generation_ttl_lazy_expiry_and_pool_reuse(rng):
    cfg = GenerationConfig(generations=2, capacity=1024, stash_slots=32,
                           backend="jnp", ttl=10.0)
    gf = GenerationalFilter(cfg, now=0.0)
    keys = random_keys(rng, 600)
    assert gf.insert(keys, now=0.0).all()
    assert gf.lookup(keys, now=9.9).all()
    # lazy: no advance() call, yet an expired generation answers nothing
    assert not gf.lookup(keys, now=10.1).any()
    assert gf.stats.expirations == 0        # not reclaimed yet
    assert gf.advance(now=10.1) == 1        # now it is
    assert gf.stats.expirations == 1
    # the ring keeps running on the recycled pool buffer
    k2 = random_keys(rng, 600)
    assert gf.insert(k2, now=11.0).all()
    assert gf.lookup(k2, now=12.0).all()
    assert gf.pool.shape == gf.active.state.table.shape


def test_generation_insert_failure_rotates_and_retries(rng):
    """A burst larger than table+stash rotates early and retries once —
    ok stays all-True and the stream keeps accepting."""
    cfg = GenerationConfig(generations=2, capacity=256, stash_slots=16,
                           backend="jnp", evict_rounds=4, o_max=2.0,
                           stash_high=2.0)   # disable proactive rotation
    gf = GenerationalFilter(cfg, now=0.0)
    keys = random_keys(rng, 400)             # > capacity + stash
    ok = gf.insert(keys, now=0.0)
    assert gf.stats.rotate_retries > 0
    assert gf.stats.rotations >= 1
    assert ok.all(), "retry in the fresh generation must land the residue"
    assert gf.lookup(keys, now=0.0).all()


# ----------------------------------------------------------- admission ---


def test_admission_controller_hysteresis(rng):
    cfg = GenerationConfig(generations=2, capacity=512, stash_slots=64,
                           backend="jnp", evict_rounds=4,
                           o_max=0.97, stash_high=2.0)
    gf = GenerationalFilter(cfg, now=0.0)
    ctl = AdmissionController(gf, AdmissionConfig(high_water=0.35,
                                                  low_water=0.1))
    assert ctl.admit(), "idle filter admits"
    gf.insert(random_keys(rng, 480), now=0.0)    # ~0.94 fill (+ spills)
    assert ctl.signal() >= 0.35
    assert not ctl.admit(), "congested filter trips"
    assert ctl.deferred == 1
    gf.rotate(now=1.0)                           # congestion relieved
    assert ctl.signal() <= 0.1
    assert ctl.admit(), "hysteresis resets below low water"
    # signal math is the documented weighted sum
    a = AdmissionConfig(stash_weight=0.5, fill_weight=0.5)
    assert congestion_signal(0.4, 0.8, a) == pytest.approx(0.6)


def test_admission_observe_eof_accelerates_window(rng):
    """observe_eof inflates marked ops by (1 + signal): a congested stream
    must close the EOF monitoring window in fewer observe calls."""
    from repro.core.policy import EofPolicy

    def drive(signal_value):
        cfg = GenerationConfig(generations=2, capacity=512, backend="jnp")
        ctl = AdmissionController(GenerationalFilter(cfg, now=0.0))
        ctl.signal = lambda: signal_value          # pin the congestion
        pol = EofPolicy(c_min=64)
        pol.observe(items=90, capacity=100, ops=1)  # arm the window
        calls = 0
        while calls < 1000:
            calls += 1
            if ctl.observe_eof(pol, items=90, capacity=100, ops=7):
                break
        return pol.t_cur

    # same number of observe calls -> congested run accumulates ~2x the
    # marked ops (and the first resize happens with a larger t_cur)
    assert drive(1.0) > drive(0.0)


def test_scheduler_admission_defers_and_drains(rng):
    """ContinuousBatcher + AdmissionController: submits defer while the
    filter is congested, and a fully-starved batcher recovers on its own —
    the drain path ages the filter (advance, else rotate) when everything
    is deferred and nothing else can move the congestion signal."""
    import dataclasses as dc
    from repro.configs import get_smoke_config
    from repro.models import Transformer
    from repro.serving.scheduler import ContinuousBatcher, Request

    cfg = dc.replace(get_smoke_config("gemma3_1b"), dtype="float32")
    model = Transformer(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    gcfg = GenerationConfig(generations=2, capacity=512, stash_slots=64,
                            backend="jnp", evict_rounds=4,
                            o_max=0.97, stash_high=2.0)
    gf = GenerationalFilter(gcfg, now=0.0)
    ctl = AdmissionController(gf, AdmissionConfig(high_water=0.35,
                                                  low_water=0.1))
    b = ContinuousBatcher(model, params, slots=2, cache_len=64, block=16,
                          admission=ctl)
    gf.insert(random_keys(rng, 480), now=0.0)    # congest the filter
    prompt = rng.randint(0, cfg.vocab_size, 32).astype(np.int32)
    assert not b.submit(Request(rid=0, prompt=prompt, max_new=2))
    assert b.stats.deferred == 1 and len(b.deferred) == 1
    assert b.congestion > 0.35
    # NO manual relief: the batcher is fully starved (everything deferred),
    # so its drain path must age the filter itself and recover.
    stats = b.run_until_drained()
    assert stats.finished == 1
    assert gf.stats.rotations >= 1, "starved drain must rotate the filter"
    assert not b.deferred and not b.queue
    # polling did not inflate the controller's per-request counters
    assert ctl.deferred == 1


def test_generational_prefix_index_promotes_hot_blocks(rng):
    """A continuously-matched prefix survives rotation: match_prefix
    promotes blocks found only in aging generations into the active one
    (multi-level promote-on-read), so hot prefixes never age out."""
    from repro.serving.kvcache import GenerationalPrefixIndex
    idx = GenerationalPrefixIndex(block=32, backend="jnp", capacity=1024,
                                  generations=2, now=0.0)
    hot = rng.randint(0, 1000, size=128).astype(np.uint32)
    idx.admit(hot, now=0.0)
    for t in range(1, 4):                    # 3 rotations > K=2 generations
        assert idx.match_prefix(hot, now=float(t)) == 4   # promotes
        idx.filt.rotate(now=float(t))
    assert idx.match_prefix(hot, now=10.0) == 4, \
        "hot prefix must survive arbitrary rotations via promotion"
    # an unmatched prefix admitted at t=0 would be gone by now
    cold = rng.randint(0, 1000, size=128).astype(np.uint32)
    idx2 = GenerationalPrefixIndex(block=32, backend="jnp", capacity=1024,
                                   generations=2, now=0.0)
    idx2.admit(cold, now=0.0)
    for t in range(1, 4):
        idx2.filt.rotate(now=float(t))
    assert idx2.match_prefix(cold, now=10.0) == 0


# ---------------------------------------------- distributed backend flag --


def test_distributed_backend_flag_pallas_parity(rng):
    """The backend flag reaches the shard-local probe: 'pallas' runs the
    fused kernel inside shard_map (rep-check relaxed) and agrees with jnp
    bit-for-bit.  Single-device mesh — the 8-device routing test lives in
    test_distributed_ocf.py."""
    from repro.core import distributed as dist
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    keys = random_keys(rng, 1024)
    hi, lo = _pair(keys)
    st = jf.make_state(256, 4)
    st, _ = jf.bulk_insert(st, hi, lo, fp_bits=16)
    sh = dist.ShardedFilterState(tables=st.table[None])
    h_j, _ = dist.distributed_lookup(mesh, "data", sh, hi, lo, fp_bits=16,
                                     backend="jnp")
    h_p, _ = dist.distributed_lookup(mesh, "data", sh, hi, lo, fp_bits=16,
                                     backend="pallas")
    np.testing.assert_array_equal(np.asarray(h_j), np.asarray(h_p))
    r_j = dist.replicated_lookup(sh.tables, hi, lo, fp_bits=16,
                                 backend="jnp")
    r_p = dist.replicated_lookup(sh.tables, hi, lo, fp_bits=16,
                                 backend="pallas")
    np.testing.assert_array_equal(np.asarray(r_j), np.asarray(r_p))


# --------------------------------------------------- evict-round config --


def test_evict_rounds_derive_from_load():
    """The round budget is a function of the operating load, pow2-rounded:
    the ROADMAP's flat 32 becomes the o_max=0.85 point of a curve that
    yields the tests' 64 at 0.9 without ad-hoc overrides."""
    assert evict_rounds_for_load(0.85) == 32
    assert evict_rounds_for_load(0.9) == 64
    assert evict_rounds_for_load(0.95) == 128
    assert evict_rounds_for_load(0.5) == 8
    assert evict_rounds_for_load(0.999) == 256          # clamped
    assert FilterOps().evict_rounds == 32               # default load
    assert OcfConfig().make_filter_ops().evict_rounds == 32
    assert OcfConfig(o_max=0.9).make_filter_ops().evict_rounds == 64
    assert OcfConfig(evict_rounds=16).make_filter_ops().evict_rounds == 16
    g = GenerationConfig(o_max=0.9).make_filter_ops()
    assert g.evict_rounds == 64


def test_ocf_stash_absorbs_storm_without_emergency_grow(rng):
    """OcfConfig.stash_slots: a high-load burst that would have triggered
    failed_inserts + emergency grow parks in the stash instead; lookups
    stay exact and deletes stay safe."""
    keys = random_keys(rng, 1900)
    cfg = OcfConfig(capacity=2048, mode="PRE", backend="pallas",
                    evict_rounds=4, stash_slots=128, o_max=0.98)
    ocf = OCF(cfg)
    ocf.insert(keys)
    assert ocf.stats.stash_spills > 0, "storm must exercise the stash"
    assert ocf.stats.failed_inserts == 0
    assert ocf.stats.resizes == 0
    assert ocf.lookup(keys).all()
    present = ocf.delete(keys[:500])
    assert present.all()
    assert ocf.lookup(keys[500:]).all()


# ------------------------------------------------------------- guards ----


def test_empty_batches_streaming(rng):
    e = jnp.zeros((0,), jnp.uint32)
    st = jf.make_state(64, 4)
    stash = make_stash(16)
    t, s, ok = kops.filter_insert(st.table, e, e, fp_bits=16,
                                  evict_rounds=8, stash=stash,
                                  use_pallas="always")
    assert np.asarray(ok).shape == (0,)
    assert not np.asarray(t).any() and not np.asarray(s).any()
    hit = kops.filter_lookup(st.table, e, e, fp_bits=16, stash=stash,
                             use_pallas="always")
    assert np.asarray(hit).shape == (0,)
    gf = GenerationalFilter(GenerationConfig(generations=2, capacity=512,
                                             backend="jnp"), now=0.0)
    empty = np.zeros((0,), np.uint64)
    assert gf.insert(empty, now=0.0).shape == (0,)
    assert gf.lookup(empty, now=0.0).shape == (0,)
    fops = FilterOps(fp_bits=16, backend="pallas")
    st2, s2, ok2 = fops.insert_spill(st, stash, e, e)
    assert np.asarray(ok2).shape == (0,) and int(st2.count) == 0
    assert np.asarray(fops.lookup_with_stash(st, stash, e, e)).shape == (0,)
