"""The traffic generator and the key streams: determinism under the seed,
the same work for every seed, and keys that never collide across classes."""
import numpy as np
import pytest

from bench import keys as K
from bench import traffic

OPEN = {"loop": "open", "rate_per_s": 400.0,
        "kinds": {"lookup": 0.95, "insert": 0.05},
        "keys_per_request": {"min": 32, "max": 512},
        "lookup": {"present_share": 0.5, "present": "latest",
                   "zipf_theta": 0.99}}
BIG_SEED = 2 ** 31 + 99


def _placed(n=50_000, seed=0):
    return np.random.default_rng(seed).random(n) < 0.9


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_open_loop_deterministic(seed):
    a = traffic.open_loop(OPEN, seed, 2.0, _placed())
    b = traffic.open_loop(OPEN, seed, 2.0, _placed())
    assert np.array_equal(a.due, b.due) and np.array_equal(a.kind, b.kind)
    for x, y in zip(a.idx + a.cls, b.idx + b.cls):
        assert np.array_equal(x, y)


def test_open_loop_same_work_for_every_seed():
    a = traffic.open_loop(OPEN, 1, 3.0, _placed())
    b = traffic.open_loop(OPEN, 2, 3.0, _placed())
    assert len(a) == len(b) == 1200
    assert not np.array_equal(a.due, b.due)
    assert np.allclose(np.sort(np.diff(a.due, prepend=0)),
                       np.sort(np.diff(b.due, prepend=0)))
    assert sorted(i.size for i in a.idx) == sorted(i.size for i in b.idx)
    assert (a.kind == "insert").sum() == (b.kind == "insert").sum() == 60
    # the window's last arrival lands near its end: the rate is as stated
    assert 2.8 < a.due[-1] <= 3.0
    sizes = np.array([i.size for i in a.idx])
    assert sizes.min() >= 32 and sizes.max() <= 512
    assert 160 < sizes.mean() < 185


def test_open_loop_keys_by_class():
    placed = _placed()
    ol = traffic.open_loop(OPEN, 3, 2.0, placed)
    cls, idx = np.concatenate(ol.cls), np.concatenate(ol.idx)
    look = np.concatenate([c for c, k in zip(ol.cls, ol.kind)
                           if k == "lookup"])
    assert abs((look == K.ABSENT).mean() - 0.5) < 0.02
    mem = idx[cls == K.MEMBER]
    assert placed[mem].all()                     # only placed keys drawn
    # latest: draws favour the recent end of the stream, the window's own
    # inserts first
    assert np.median(placed.size - mem) < placed.size / 4
    assert (look == K.FRESH).sum() > 0.1 * (look != K.ABSENT).sum()
    fresh_ins = np.concatenate([i for i, k in zip(ol.idx, ol.kind)
                                if k == "insert"])
    assert np.array_equal(fresh_ins, np.arange(fresh_ins.size))
    absent = idx[cls == K.ABSENT]
    assert np.unique(absent).size == absent.size


def test_zipf_head_ratio():
    z = traffic.Zipf(0.99)
    r = z.sample(np.random.default_rng(0).random(400_000),
                 np.full(400_000, 10 ** 6))
    c0, c1 = (r == 0).sum(), (r == 1).sum()
    assert abs(c0 / c1 - 2 ** 0.99) < 0.1
    assert r.max() < 10 ** 6


def test_keys_numpy_and_device_agree():
    import jax.numpy as jnp
    idx = np.array([0, 1, 2, 12345, 2 ** 32 - 1], np.int64)
    rk = K.round_keys(BIG_SEED)
    hi, lo = K.keys_hilo_np(BIG_SEED, K.MEMBER, idx)
    dhi, dlo = K.keys_hilo_jnp(jnp.asarray(rk), K.MEMBER,
                               jnp.asarray(idx.astype(np.uint32)))
    assert np.array_equal(hi, np.asarray(dhi))
    assert np.array_equal(lo, np.asarray(dlo))


def test_keys_distinct_across_classes_and_seeds():
    idx = np.arange(100_000)
    ks = [K.keys_np(s, c, idx) for s in (1, 2)
          for c in (K.MEMBER, K.ABSENT, K.FRESH)]
    allk = np.concatenate(ks)
    assert np.unique(allk).size == allk.size
    assert not np.array_equal(K.round_keys(1), K.round_keys(1 + 2 ** 32))


def test_committed_mix_sends_one_key_per_request():
    """The cell's mix is YCSB's: every operation one key."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "traffic", "read_latest.json")
    with open(path) as f:
        mix = json.load(f)
    a = traffic.open_loop(mix, BIG_SEED, 2.0, _placed())
    assert len(a) == round(2.0 * mix["rate_per_s"])
    assert all(i.size == 1 for i in a.idx)
    assert abs((a.kind == "insert").sum() - 0.05 * len(a)) < 1
