import itertools
import threading
import time

import numpy as np
import pytest

from chipbench import loadgen


class FakeEngine:
    """Answers every queued request on each step, taking ``service`` s of
    real time per step; the first step after ``stall_at`` s takes
    ``stall`` s more; ``fail`` ids end in an error and ``shed`` ids are
    shed (both not ok)."""

    def __init__(self, service=0.01, stall_at=None, stall=0.0, fail=(),
                 shed=()):
        self.service, self.stall_at, self.stall = service, stall_at, stall
        self.fail, self.shed = set(fail), set(shed)
        self.queue, self.ids = [], itertools.count()
        self.lock = threading.Lock()
        self.t0 = time.monotonic()

    def submit(self, *_a, **_k):
        with self.lock:
            rid = next(self.ids)
            self.queue.append(rid)
        return rid

    def step(self):
        with self.lock:
            out, self.queue = self.queue, []
        if not out:
            return []
        time.sleep(self.service)
        if (self.stall_at is not None
                and time.monotonic() - self.t0 >= self.stall_at):
            time.sleep(self.stall)
            self.stall_at = None
        return [Result(rid, rid not in self.fail and rid not in self.shed)
                for rid in out]


class Result:
    def __init__(self, rid, ok):
        self.request_id, self.ok = rid, ok


def sched(n, gap):
    return loadgen.Schedule(due_s=np.arange(n) * gap,
                            tenant=np.zeros(n, int), row=np.zeros(n, int))


def run_open(engine, s, seconds, **kw):
    return loadgen.drive_open(engine, s, lambda i: engine.submit(i),
                              seconds, **kw)


def test_same_seed_same_schedule_and_same_work_across_seeds():
    mix = {"loop": "open", "rate_rps": 20.0, "tenants": 8,
           "tenant_zipf": 0.99}
    a = loadgen.make_schedule(mix, 10, 1000, np.random.default_rng(5))
    b = loadgen.make_schedule(mix, 10, 1000, np.random.default_rng(5))
    c = loadgen.make_schedule(mix, 10, 1000, np.random.default_rng(6))
    for x, y in ((a.due_s, b.due_s), (a.tenant, b.tenant), (a.row, b.row)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a.due_s, c.due_s)
    # another seed: the same gaps and tenant counts, in another order
    assert len(a) == len(c) == 200
    def gaps(x):
        return np.sort(np.diff(np.concatenate([[0.0], x.due_s])))

    assert np.allclose(gaps(a), gaps(c))
    assert np.array_equal(np.bincount(a.tenant), np.bincount(c.tenant))
    assert a.due_s[-1] < 10.0


def test_tenant_counts_are_zipf():
    counts = loadgen.zipf_counts(1000, 8, 0.99)
    assert counts.sum() == 1000
    assert list(counts) == sorted(counts, reverse=True)
    assert counts[0] / counts[1] == pytest.approx(2 ** 0.99, rel=0.05)


def test_a_stall_delays_the_latency_of_later_requests():
    eng = FakeEngine(service=0.005, stall_at=0.25, stall=0.5)
    lg = run_open(eng, sched(20, 0.05), 1.0)
    lat = lg.latencies(lg.attempted())
    # the generator kept its schedule (its own thread) ...
    lag = lg.submit - lg.due
    assert lag.max() < 0.1
    # ... so requests due during the stall are timed from their due time
    # and carry the wait: the one due just after the stall began waits
    # nearly all of it
    assert np.median(lat[:4]) < 0.1
    assert lat[6:10].max() > 0.3
    assert np.all(lg.ok)


def test_failed_and_shed_requests_count_as_missing():
    eng = FakeEngine(fail={3}, shed={7})
    lg = run_open(eng, sched(10, 0.02), 0.2)
    idx = lg.attempted()
    lat = lg.latencies(idx)
    assert not lg.ok[3] and not lg.ok[7]
    ok_max = np.delete(lat, [3, 7]).max()
    assert lat[3] > ok_max and lat[7] > ok_max
    assert lat[3] == lg.t_stop - lg.due[3]


def test_a_request_that_never_returns_counts_as_missing():
    eng = FakeEngine()
    real_step = eng.step

    def step():
        out = real_step()
        return [r for r in out if r.request_id != 4]

    eng.step = step
    lg = run_open(eng, sched(8, 0.02), 0.16, grace_s=0.3)
    assert lg.gave_up
    assert np.isnan(lg.done[4])
    lat = lg.latencies(lg.attempted())
    assert lat[4] >= 0.3


def test_closed_loop_keeps_clients_busy():
    eng = FakeEngine(service=0.05)
    s = loadgen.Schedule(due_s=None, tenant=np.zeros(1000, int),
                         row=np.zeros(1000, int))
    lg = loadgen.drive_closed(eng, s, lambda i: eng.submit(i), 0.5, 4)
    # each 0.05 s step answers all 4 clients, which resend at once: about
    # ten rounds of four in the half second
    idx = lg.attempted()
    assert len(idx) % 4 == 0 and 32 <= len(idx) <= 44
    assert np.all(lg.ok[idx])
    assert np.all(lg.submit[idx] == lg.due[idx])


def synthetic_log(due, done, seconds):
    n = len(due)
    lg = loadgen.Log.empty(n, 0.0, seconds)
    lg.due[:] = due
    lg.done[:] = done
    lg.ok[:] = ~np.isnan(done)
    return lg


def test_backlog_halves_average_the_queue_over_time():
    # one request due every 0.5 s, each answered 1 s later: a steady
    # backlog of 2 (1.5 over the first second)
    due = np.arange(0, 20, 0.5)
    lg = synthetic_log(due, due + 1.0, 20.0)
    first, second = loadgen.backlog_halves(lg)
    assert first == pytest.approx(1.95, abs=0.02)
    assert second == pytest.approx(2.0, abs=0.02)
    # the same arrivals answered at 1.5 per second: the backlog grows
    done = np.arange(len(due)) / 1.5 + 1.0
    grow = loadgen.backlog_halves(synthetic_log(due, done, 20.0))
    assert not loadgen.sustained([grow])
    assert loadgen.sustained([(first, second)])
    # never answered: counts as backlog to the close
    done = due + 1.0
    done[-10:] = np.nan
    assert loadgen.backlog_halves(synthetic_log(due, done, 20.0))[1] > second + 1.5


@pytest.mark.parametrize("halves, held", [
    ([(2.0, 2.2), (1.8, 2.9)], True),       # steady queue, noisy repeats
    ([(0.1, 0.9), (0.2, 1.1)], True),       # near idle: the slack holds
    ([(4.0, 9.0), (5.0, 8.0)], False),      # grows about 2x in each
    ([(3.0, 3.5), (3.0, 14.0)], False),     # one repeat runs away
])
def test_sustained_rule(halves, held):
    assert loadgen.sustained(halves) is held


def test_knee_is_the_last_rate_below_the_first_that_is_not_held():
    assert loadgen.knee([8, 10, 12, 14], [True, True, False, True]) == 10
    assert loadgen.knee([8, 10], [True, True]) == 10
    assert loadgen.knee([8, 10], [False, True]) is None


def test_the_engine_loops_longest_step_is_kept():
    eng = FakeEngine(service=0.002, stall_at=0.2, stall=0.3)
    lg = run_open(eng, sched(30, 0.02), 0.6)
    assert 0.3 <= lg.longest_step_s < 0.5
