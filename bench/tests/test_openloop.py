"""Open-loop arithmetic on a fake clock: requests go out when due, the
harness flushes while it waits, and stamps each request done on its own
clock when the answers are back, so latency runs from the due time."""
import numpy as np

from bench import openloop


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-6                   # a read takes a microsecond
        return self.t

    def sleep(self, s):
        self.t += s


class FakeSystem:
    """Double-buffered like the batcher: a submit answers the request
    before it; a flush answers the one in flight."""

    def __init__(self, clock, n, submit_s=0.002, flush_s=0.001):
        self.clock, self.log = clock, []
        self.results = [None] * n
        self.inflight = None
        self.submit_s, self.flush_s = submit_s, flush_s

    def _answer(self):
        if self.inflight is not None:
            self.results[self.inflight] = np.ones(1, bool)
            self.inflight = None

    def submit(self, j):
        self.log.append(("submit", j, round(self.clock.t - 100.0, 6)))
        self.clock.t += self.submit_s
        self._answer()
        self.inflight = j

    def flush(self):
        self.log.append(("flush", round(self.clock.t - 100.0, 6)))
        self.clock.t += self.flush_s
        self._answer()

    def answers(self, j):
        return self.results[j]


def _run(due, system, clock):
    return openloop.run(due, system.submit, system.flush, system.answers,
                        clock=clock, sleep=clock.sleep)


def test_submits_when_due_and_flushes_while_idle():
    clock = FakeClock()
    due = np.array([0.0, 0.010, 0.010, 0.050])
    sys_ = FakeSystem(clock, due.size)
    t0, sent, done = _run(due, sys_, clock)
    assert abs(t0 - 100.0) < 1e-5
    kinds = [e[0] for e in sys_.log]
    # after every burst of due requests, one flush before waiting
    assert kinds == ["submit", "flush", "submit", "submit", "flush",
                     "submit", "flush"]
    assert np.all(sent >= due)
    # the second request of the burst went out late, behind the first
    assert abs((sent[2] - due[2]) - 0.002) < 1e-4


def test_latency_from_due_not_from_submit():
    """Done is stamped by the harness when the answers arrive."""
    clock = FakeClock()
    due = np.array([0.0, 0.010, 0.010, 0.050])
    sys_ = FakeSystem(clock, due.size)
    _t0, sent, done = _run(due, sys_, clock)
    lat = done - due
    # request 0: its 2 ms submit, then a 1 ms flush answers it
    assert abs(lat[0] - 0.003) < 1e-4
    # request 1 is answered by request 2's submit, 4 ms after it was due
    assert abs(lat[1] - 0.004) < 1e-4
    # request 2 went out 2 ms late and waited for the flush behind it
    assert abs(lat[2] - 0.005) < 1e-4
    assert np.all(done >= sent)


def test_a_stall_counts_against_every_request_behind_it():
    clock = FakeClock()
    due = np.array([0.0, 0.001, 0.002, 0.003])
    sys_ = FakeSystem(clock, due.size, submit_s=0.020)   # one slow submit
    _t0, sent, done = _run(due, sys_, clock)
    lat = done - due
    assert np.all(lat[1:] > 0.020)
    assert np.all(np.diff(done) > 0)


def test_a_request_never_answered_stays_open():
    clock = FakeClock()
    due = np.array([0.0, 0.005])
    sys_ = FakeSystem(clock, due.size)
    sys_.answers = lambda j: None if j == 1 else sys_.results[j]
    _t0, _sent, done = _run(due, sys_, clock)
    assert np.isfinite(done[0]) and np.isnan(done[1])


def test_percentiles_and_tail_samples():
    x = np.arange(1, 1001, dtype=float)
    assert openloop.percentile(x, 50) == 500.5
    assert abs(openloop.percentile(x, 99) - 990.01) < 1e-9
    assert openloop.beyond(1000, 99) == 10
    assert openloop.beyond(20000, 99) == 200
    assert openloop.trend(np.ones(50)) == 1.0
    assert openloop.trend(np.arange(1, 11, dtype=float)) == 9.5 / 1.5
