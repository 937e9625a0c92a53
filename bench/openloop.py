"""Open-loop timing: requests go out when due, whatever came back.

``run`` submits request ``j`` at ``due[j]`` seconds after the window opens
and, whenever the next request is not yet due, calls ``flush`` so that no
answer waits for the next arrival (a double-buffered submit path harvests
wave k only when wave k+1 is submitted).

Completion is stamped here, on the harness's own clock, never taken from
the system under test: after every ``submit`` and ``flush`` the harness
asks ``answers(j)`` of each request still open, brings what it returns
onto the host, and stamps the request done.  That is also when a
single-threaded client gets control back.  A request's latency runs from
the moment it was due to that stamp, so a stall counts against every
request that queued behind it.
"""
from __future__ import annotations

import time

import numpy as np

SPIN_S = 0.0003      # sleep until this close to a due time, then spin


def run(due, submit, flush, answers, *, clock=time.perf_counter,
        sleep=time.sleep, wait_span=None):
    """-> (t0, sent[n], done[n]): the window's start on ``clock``, and when
    each request went out and when its answers were on the host, in seconds
    after it (NaN for a request never answered).

    ``answers(j)`` returns request ``j``'s answers once the system has
    handed them back, else None."""
    n = len(due)
    sent = np.empty(n)
    done = np.full(n, np.nan)
    t0 = clock()
    open_ = []

    def collect():
        still = []
        for j in open_:
            a = answers(j)
            if a is None:
                still.append(j)
                continue
            np.asarray(a)                # on the host, not a promise of it
            done[j] = clock() - t0
        open_[:] = still

    j, idle = 0, True
    while j < n:
        now = clock() - t0
        if now >= due[j]:
            sent[j] = now
            submit(j)
            open_.append(j)
            j += 1
            idle = False
            collect()
        elif not idle:
            flush()
            collect()
            idle = True
        elif due[j] - now > SPIN_S:
            if wait_span is None:
                sleep(due[j] - now - SPIN_S)
            else:
                with wait_span():
                    sleep(due[j] - now - SPIN_S)
    flush()
    collect()
    return t0, sent, done


def percentile(x, q: float) -> float:
    """The ``q``-th percentile (numpy's linear interpolation)."""
    return float(np.percentile(np.asarray(x, np.float64), q))


def beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(np.floor(n * (1 - q / 100.0)))


def trend(lat) -> float:
    """Mean latency of the last fifth of the requests over the first
    fifth's: near 1 while the system keeps up, growing with a backlog."""
    lat = np.asarray(lat, np.float64)
    fifth = max(1, lat.size // 5)
    return float(lat[-fifth:].mean() / lat[:fifth].mean())
