"""The closed-loop generator: one caller, one call in flight, each call
issued once the previous one has its answers.

A mix with ``"loop": "closed"`` gives the keys per call and a cycle of call
kinds (``cycle``: calls per kind); each cycle holds those counts in its own
seeded order.  Keys are named by (class, index) of ``bench.keys``:

* an insert takes the next fresh keys (``FRESH``, numbered from 0);
* a delete takes the oldest live keys: the set-up's members in stream
  order (those it placed), then the window's inserts in insert order, as a
  store under FIFO compaction with a TTL drops its data oldest first;
* a lookup is half absent keys (``ABSENT``, numbered from
  ``absent_base``) and half live keys drawn by recency (YCSB's
  SkewedLatest: a zipfian of ``zipf_theta`` over recency rank, the newest
  first; a draw that lands on a key the set-up did not place is drawn
  again), in a seeded order.

The generator takes every insert and delete it issued as applied: what the
system acknowledged is the reference's to judge, so a seed gives the same
calls whatever the answers.
"""
from __future__ import annotations

import numpy as np

from bench import keys as K
from bench import traffic


class ClosedLoop:
    def __init__(self, spec: dict, seed: int, placed: np.ndarray, *,
                 absent_base: int = 0):
        if (spec.get("loop"), spec.get("in_flight"), spec.get("insert"),
                spec.get("delete"), spec["lookup"].get("present")) != (
                "closed", 1, "fresh", "oldest", "latest"):
            raise ValueError("the closed loop runs one call in flight, "
                             "fresh inserts, oldest-first deletes and "
                             "latest-first lookups")
        self.per_call = int(spec["keys_per_call"])
        names = sorted(spec["cycle"])
        self._cycle = np.repeat(np.array(names),
                                [spec["cycle"][k] for k in names])
        lk = spec["lookup"]
        self._present = int(round(lk["present_share"] * self.per_call))
        self._zipf = traffic.Zipf(lk["zipf_theta"])
        self._order = traffic.rng_for(seed, 11)
        self._pick = traffic.rng_for(seed, 12)
        self.placed = placed
        self._kinds = []
        self.fresh = 0          # fresh keys inserted
        self.fresh_gone = 0     # of those, deleted (the oldest first)
        self.member_pos = 0     # stream position of the oldest live member
        self.absent = absent_base

    def next(self):
        """The next call -> (kind, classes uint8[n], indices int64[n])."""
        if not self._kinds:
            self._kinds = list(self._order.permutation(self._cycle))
        kind = str(self._kinds.pop(0))
        n = self.per_call
        if kind == "insert":
            idx = np.arange(self.fresh, self.fresh + n, dtype=np.int64)
            self.fresh += n
            return kind, np.full(n, K.FRESH, np.uint8), idx
        if kind == "delete":
            return (kind, *self._oldest(n))
        if kind != "lookup":
            raise ValueError(f"closed loop: unknown kind {kind!r}")
        present = self._pick.permutation(n) < self._present
        cls = np.full(n, K.ABSENT, np.uint8)
        idx = np.empty(n, np.int64)
        idx[~present] = np.arange(self.absent, self.absent + n - self._present)
        self.absent += n - self._present
        cls[present], idx[present] = self._latest(self._present)
        return kind, cls, idx

    def _oldest(self, n: int):
        """The ``n`` oldest live keys: members, then fresh keys."""
        pos, at = [], self.member_pos
        need = n
        while need and at < self.placed.size:
            span = self.placed[at:at + need + need // 8 + 64]
            got = np.flatnonzero(span)[:need] + at
            pos.append(got)
            need -= got.size
            at = int(got[-1]) + 1 if need == 0 else at + span.size
        self.member_pos = at
        members = np.concatenate(pos) if pos else np.zeros(0, np.int64)
        fresh = np.arange(self.fresh_gone, self.fresh_gone + need,
                          dtype=np.int64)
        if self.fresh_gone + need > self.fresh:
            raise ValueError("closed loop: fewer live keys than a delete")
        self.fresh_gone += need
        cls = np.concatenate([np.full(members.size, K.MEMBER, np.uint8),
                              np.full(need, K.FRESH, np.uint8)])
        return cls, np.concatenate([members.astype(np.int64), fresh])

    def _latest(self, n: int):
        """``n`` live keys drawn zipfian by recency, the newest first."""
        n_fresh = self.fresh - self.fresh_gone
        n_items = n_fresh + self.placed.size - self.member_pos
        cls = np.empty(n, np.uint8)
        idx = np.empty(n, np.int64)
        want = np.arange(n)
        while want.size:
            r = self._zipf.sample(self._pick.random(want.size),
                                  np.full(want.size, n_items))
            is_fresh = r < n_fresh
            m = self.placed.size - 1 - (r - n_fresh)
            ok = ~is_fresh & self.placed[np.where(is_fresh, 0, m)]
            cls[want[is_fresh]] = K.FRESH
            idx[want[is_fresh]] = self.fresh - 1 - r[is_fresh]
            cls[want[ok]] = K.MEMBER
            idx[want[ok]] = m[ok]
            want = want[~is_fresh & ~ok]
        return cls, idx
