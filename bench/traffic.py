"""The one traffic generator: a mix file of parameters in, requests out.

A mix is ``bench/traffic/<name>.json``.  Keys are named by (class, index)
of ``bench.keys``; the driver turns them into keys, and the reference
judges answers by them.

Open loop (``"loop": "open"``): ``round(rate_per_s * seconds)`` requests.
Every seed gets the same multiset of inter-arrival gaps (the exponential
distribution's quantiles at ``rate_per_s``: Poisson arrivals), of request
sizes (``keys_per_request``: log-uniform quantiles) and of kinds (the exact
shares of ``kinds``), each in its own seeded order.  So seeds change which
request comes when, not how much work a run holds.

Lookup keys are present with probability ``present_share``, else absent.
Present keys are drawn by recency (``"present": "latest"``: YCSB's
SkewedLatest, a zipfian of constant ``zipf_theta`` over recency rank, most
recent first).  Ranks count the offered member stream and the window's own
inserts; a draw that lands on a key the set-up did not place is drawn
again.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench import keys as K

def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2 ** 64 - 1), stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def request_sizes(spec: dict, n: int, rng) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    if lo == hi:
        return np.full(n, lo, np.int64)
    q = _quantiles(n)
    size = np.floor(np.exp(math.log(lo) + q * (math.log(hi + 1)
                                                 - math.log(lo))))
    return rng.permutation(np.clip(size, lo, hi).astype(np.int64))


def exact_kinds(shares: dict, n: int, rng) -> np.ndarray:
    """``n`` kind names holding the exact shares (largest remainder)."""
    names = sorted(shares)
    want = np.array([shares[k] * n for k in names])
    cnt = np.floor(want).astype(np.int64)
    for i in np.argsort(-(want - cnt), kind="stable")[:n - cnt.sum()]:
        cnt[i] += 1
    return rng.permutation(np.repeat(np.array(names), cnt))


# ------------------------------------------------------------ zipfian --


class Zipf:
    """YCSB's ZipfianGenerator (Gray et al.) over ``n`` items, vectorised
    over ``n``; zeta(n) exact up to 2^17 terms plus the integral of the
    tail (error far below one part in 10^9)."""

    _HEAD = 1 << 17

    def __init__(self, theta: float):
        self.theta = theta
        self.alpha = 1.0 / (1.0 - theta)
        self._cum = np.cumsum(np.arange(1, self._HEAD + 1) ** -theta)
        self.zeta2 = 1.0 + 0.5 ** theta

    def zeta(self, n: np.ndarray) -> np.ndarray:
        n = np.asarray(n, np.float64)
        head = self._cum[np.minimum(n, self._HEAD).astype(np.int64) - 1]
        e = 1.0 - self.theta
        tail = ((n + 0.5) ** e - (self._HEAD + 0.5) ** e) / e
        return head + np.where(n > self._HEAD, tail, 0.0)

    def sample(self, u: np.ndarray, n: np.ndarray) -> np.ndarray:
        """Ranks in [0, n) for uniforms ``u``: rank 0 is the most likely."""
        n = np.asarray(n, np.float64)
        zn = self.zeta(n)
        eta = (1 - (2.0 / n) ** (1 - self.theta)) / (1 - self.zeta2 / zn)
        uz = u * zn
        r = np.floor(n * (eta * u - eta + 1) ** self.alpha)
        r = np.where(uz < 1.0, 0, np.where(uz < self.zeta2, 1, r))
        return np.minimum(r, n - 1).astype(np.int64)


# ---------------------------------------------------------- open loop --


@dataclasses.dataclass
class OpenLoop:
    due: np.ndarray          # float64[n] seconds from the window's start
    kind: np.ndarray         # str[n]
    cls: list                # per request: uint8[size] key classes
    idx: list                # per request: int64[size] key indices

    def __len__(self):
        return self.due.size


def open_loop(spec: dict, seed: int, seconds: float, placed: np.ndarray,
              fresh_base: int = 0) -> OpenLoop:
    """The requests of one window; ``placed`` flags the offered member
    stream (True where the set-up placed the key); fresh inserts number
    from ``fresh_base``."""
    n = max(1, round(spec["rate_per_s"] * seconds))
    gaps = rng_for(seed, 1).permutation(
        -np.log1p(-_quantiles(n)) / spec["rate_per_s"])
    due = np.cumsum(gaps)
    sizes = request_sizes(spec["keys_per_request"], n, rng_for(seed, 2))
    kind = exact_kinds(spec["kinds"], n, rng_for(seed, 3))
    lk = spec.get("lookup", {})
    pick = rng_for(seed, 4)
    zipf = Zipf(lk.get("zipf_theta", 0.99))
    n_offered = placed.size
    fresh, absent = fresh_base, 0
    cls, idx = [], []
    for j in range(n):
        s = int(sizes[j])
        if kind[j] == "insert":
            cls.append(np.full(s, K.FRESH, np.uint8))
            idx.append(np.arange(fresh, fresh + s, dtype=np.int64))
            fresh += s
            continue
        if kind[j] != "lookup":
            raise ValueError(f"open loop: unknown kind {kind[j]!r}")
        present = pick.random(s) < lk.get("present_share", 0.5)
        c = np.full(s, K.ABSENT, np.uint8)
        x = np.zeros(s, np.int64)
        n_abs = int((~present).sum())
        x[~present] = np.arange(absent, absent + n_abs)
        absent += n_abs
        want = np.flatnonzero(present)
        while want.size:
            if lk.get("present", "latest") != "latest":
                raise ValueError("open loop: present keys are 'latest'")
            r = zipf.sample(pick.random(want.size),
                            np.full(want.size, n_offered + fresh))
            is_fresh = r < fresh
            c[want[is_fresh]] = K.FRESH
            x[want[is_fresh]] = fresh - 1 - r[is_fresh]
            m = n_offered - 1 - (r - fresh)
            ok = ~is_fresh & placed[np.where(is_fresh, 0, m)]
            c[want[ok]] = K.MEMBER
            x[want[ok]] = m[ok]
            want = want[~is_fresh & ~ok]
        cls.append(c)
        idx.append(x)
    return OpenLoop(due, kind, cls, idx)


def keys_of(seed: int, cls: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """uint64 keys of mixed classes."""
    out = np.empty(idx.size, np.uint64)
    for c in np.unique(cls):
        sel = cls == c
        out[sel] = K.keys_np(seed, int(c), idx[sel])
    return out
