"""The plain reference: which keys are members, call by call.

A membership filter answers "present" for every acknowledged key that was
not deleted since (no false negatives), and "present" for other keys only
at its false-positive rate.  The reference holds keys by their
(class, index) of ``bench.keys`` and replays what the system acknowledged
in the order the calls were issued:

* the set-up's member stream: a key is a member where the set-up placed it;
* an insert makes its key a member from the next call on, where ``ok``;
* a delete of a member removes it from the next call on, where ``ok``;
* absent and padding keys are never members.

It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from bench import keys as K

NEVER = np.iinfo(np.int64).max


class Reference:
    def __init__(self, placed: np.ndarray):
        self.placed = placed
        self._ins, self._del, self._look = [], [], []

    def insert(self, call: int, cls, idx, ok):
        self._ins.append((call, np.asarray(cls), np.asarray(idx),
                          np.asarray(ok, bool)))

    def delete(self, call: int, cls, idx, ok):
        self._del.append((call, np.asarray(cls), np.asarray(idx),
                          np.asarray(ok, bool)))

    def lookup(self, call: int, cls, idx, answer):
        self._look.append((call, np.asarray(cls), np.asarray(idx),
                           np.asarray(answer, bool)))

    # ---------------------------------------------------------------- --

    @staticmethod
    def _cat(log):
        if not log:
            return np.zeros(0, np.int64), np.zeros(0, np.uint8), \
                np.zeros(0, np.int64), np.zeros(0, bool)
        call = np.concatenate([np.full(e[1].size, e[0], np.int64)
                               for e in log])
        return (call, np.concatenate([e[1] for e in log]),
                np.concatenate([e[2] for e in log]),
                np.concatenate([e[3] for e in log]))

    def _events(self, log, cls):
        """(index, call) of the ok events of one class, sorted by index."""
        call, c, idx, ok = self._cat(log)
        sel = ok & (c == cls)
        order = np.lexsort((call[sel], idx[sel]))
        return idx[sel][order], call[sel][order]

    @staticmethod
    def _first_before(ev_idx, ev_call, idx, call):
        """Per (idx, call): the first event of ``idx`` strictly before
        ``call`` -> its call, else NEVER (events sorted by idx, call)."""
        found = np.full(idx.size, NEVER, np.int64)
        if not ev_idx.size:
            return found
        lo = np.searchsorted(ev_idx, idx, side="left")
        hi = np.searchsorted(ev_idx, idx, side="right")
        has = hi > lo
        first = ev_call[np.minimum(lo, max(ev_call.size - 1, 0))]
        found[has & (first < call)] = first[has & (first < call)]
        return found

    def live(self, call, cls, idx) -> np.ndarray:
        """Membership of each (cls, idx) at the issue of ``call``."""
        call = np.broadcast_to(np.asarray(call, np.int64), idx.shape)
        alive = np.zeros(idx.size, bool)
        # member stream: placed at set-up, alive until a delete before call
        m = cls == K.MEMBER
        if m.any():
            mi = idx[m]
            ok = (mi < self.placed.size) & self.placed[
                np.minimum(mi, max(self.placed.size - 1, 0))]
            di, dc = self._events(self._del, K.MEMBER)
            gone = self._first_before(di, dc, mi, call[m]) != NEVER
            alive[m] = ok & ~gone
        for c in (K.FRESH, K.WARM):
            f = cls == c
            if not f.any():
                continue
            ii, ic = self._events(self._ins, c)
            di, dc = self._events(self._del, c)
            born = self._first_before(ii, ic, idx[f], call[f]) != NEVER
            gone = self._first_before(di, dc, idx[f], call[f]) != NEVER
            alive[f] = born & ~gone
        return alive

    def verdict(self) -> dict:
        """Counts over every lookup and delete the window made."""
        call, cls, idx, ans = self._cat(self._look)
        member = self.live(call, cls, idx)
        out = {"lookups": int(idx.size),
               "false_negatives": int((member & ~ans).sum()),
               "false_positives": int((~member & ans).sum()),
               "non_member_lookups": int((~member).sum())}
        out["fpr"] = out["false_positives"] / max(out["non_member_lookups"], 1)
        call, cls, idx, ok = self._cat(self._del)
        was = self.live(call, cls, idx)
        out["blind_deletes"] = int((~was).sum())
        out["delete_misses"] = int((was & ~ok).sum())
        return out

    def acked(self, cls: int) -> np.ndarray:
        """Indices of ``cls`` acknowledged by an insert and not deleted."""
        ii, _ = self._events(self._ins, cls)
        di, _ = self._events(self._del, cls)
        return np.setdiff1d(ii, di)

    def deleted(self, cls: int) -> np.ndarray:
        di, _ = self._events(self._del, cls)
        return np.unique(di)
