"""The one traffic generator: a mix file's parameters -> a schedule, and the
open- and closed-loop players that run it against a `ServeEngine`.

Adapted from the paced loop of ``benchmarks/serve_bench.run_offered_load``
with its faults repaired:

- latency runs from the time a request was *due*, not from its submit, so
  a late generator cannot hide queueing; how late each submit ran is kept
  (``Log.submit - Log.due``);
- tenants are drawn Zipf-skewed, not round-robin;
- a request that fails, is shed or never returns counts as missing every
  limit: its latency is the time the harness waited for it;
- a closed loop (C clients, each sending when its last reply returns)
  exists beside the open one.

Every seed gets the same work: the open loop's inter-arrival gaps are the
same stratified set of exponential quantiles in a seed-drawn order, and
tenant counts are fixed by the Zipf weights (largest remainder) in a
seed-drawn order.  Only which corpus row each query lies near is free.

Optional keys of a mix, each drawn after the draws above so that a mix
without them gives the same schedule:

- ``burst_factor``, ``burst_period_s``, ``burst_duty`` (open loop): the
  rate is ``burst_factor`` times ``rate_rps`` for the first ``burst_duty``
  of every period and lower for the rest, so the mean stays ``rate_rps``;
  the stratified arrival times are warped through the inverse of the
  cumulative rate (`burst_warp`), so the count and the work stay the same;
- ``topic_zipf`` (a corpus with topics): each query's topic is drawn
  Zipf(``topic_zipf``) over topic ranks (counts fixed by largest
  remainder, 0 for the uniform-topic control), then its row uniformly
  within the topic;
- ``hot_shift_s``: every this many seconds a seed-drawn permutation
  re-ranks the topics, so the hot set moves (its top topic always
  changes); a closed loop shifts by request index at ``nominal_rps``.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from chipbench.spec import SpecError

OPEN, CLOSED = "open", "closed"
BURST_KEYS = ("burst_factor", "burst_period_s", "burst_duty")
TOPIC_KEYS = ("topic_zipf", "hot_shift_s")
GRACE_S = 60.0           # how long past the window the harness waits
IDLE_SLEEP_S = 0.0005    # poll period of an idle engine loop


@dataclasses.dataclass
class Schedule:
    """What each request is: its due offset from the window start (open
    loop; None for closed), its tenant index and the corpus row its query
    lies near."""
    due_s: Optional[np.ndarray]
    tenant: np.ndarray
    row: np.ndarray

    def __len__(self) -> int:
        return len(self.tenant)


def zipf_counts(n: int, ranks: int, zipf_s: float) -> np.ndarray:
    """``n`` split over ``ranks`` by Zipf(s) weights (requests per tenant or
    per topic rank, documents per topic), rounded by largest remainder so
    the counts sum to ``n``."""
    w = 1.0 / np.arange(1, ranks + 1) ** zipf_s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return counts


def stratified_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of mean about 1/rate: the
    quantiles at (i + 1/2)/n, one per stratum."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def check_mix(mix: dict, has_topics: bool) -> None:
    """Refuse a mix whose optional keys do not fit its loop or corpus."""
    loop = mix["loop"]
    if loop not in (OPEN, CLOSED):
        raise SpecError(f"unknown loop {loop!r} in traffic mix")
    bursts = [k for k in BURST_KEYS if k in mix]
    if bursts:
        if loop != OPEN or len(bursts) != len(BURST_KEYS):
            raise SpecError(f"bursts need an open loop and all of "
                            f"{BURST_KEYS}, got {bursts} ({loop})")
        f, p, d = (float(mix[k]) for k in BURST_KEYS)
        if not (f >= 1 and p > 0 and 0 < d < 1 and f * d <= 1):
            raise SpecError(f"bursts need burst_factor >= 1, period > 0, "
                            f"0 < duty < 1 and factor * duty <= 1: "
                            f"{f}, {p}, {d}")
    topical = [k for k in TOPIC_KEYS if k in mix]
    if topical and not has_topics:
        raise SpecError(f"mix keys {topical} need a configuration whose "
                        f"corpus block has topics")
    if "hot_shift_s" in mix:
        if "topic_zipf" not in mix or not float(mix["hot_shift_s"]) > 0:
            raise SpecError("hot_shift_s needs topic_zipf and is > 0")
        if loop == CLOSED and not float(mix.get("nominal_rps", 0)) > 0:
            raise SpecError("a closed loop's hot_shift_s needs nominal_rps")


def burst_warp(tau: np.ndarray, seconds: float, factor: float,
               period: float, duty: float) -> np.ndarray:
    """Arrival times ``tau`` of a steady rate over ``[0, seconds)`` mapped
    onto a rate ``factor`` times the mean in the first ``duty`` of each
    ``period`` and ``(1 - duty factor) / (1 - duty)`` times it in the rest:
    the inverse of the cumulative rate (over the mean), stretched so that
    what was in the window stays in it."""
    low = (1.0 - duty * factor) / (1.0 - duty)
    on = duty * period                      # the burst's length
    head = factor * on                      # its share of a period's work

    def cum(t):
        k, ph = np.divmod(t, period)
        return k * period + np.where(ph < on, factor * ph,
                                     head + low * (ph - on))

    k, g = np.divmod(tau * (cum(seconds) / seconds), period)
    tail = np.divide(g - head, low, out=np.zeros_like(g), where=g >= head)
    return k * period + np.where(g < head, g / factor, on + tail)


def topic_of(mix: dict, due_s: Optional[np.ndarray], n: int, topics: int,
             rng: np.random.Generator) -> np.ndarray:
    """Each request's topic: its Zipf rank (fixed counts, seed-drawn
    order) through the ranking of its hot-set epoch."""
    rank = rng.permutation(np.repeat(
        np.arange(topics), zipf_counts(n, topics, mix["topic_zipf"])))
    shift = mix.get("hot_shift_s")
    if shift is None:
        epoch = np.zeros(n, np.int64)
    elif due_s is not None:
        epoch = np.floor(due_s / shift).astype(np.int64)
    else:
        epoch = np.arange(n) // max(1, round(mix["nominal_rps"] * shift))
    ranking = np.empty((int(epoch.max()) + 1, topics), np.int64)
    for e in range(len(ranking)):
        ranking[e] = rng.permutation(topics)
        if e and topics > 1 and ranking[e, 0] == ranking[e - 1, 0]:
            ranking[e, [0, 1]] = ranking[e, [1, 0]]
    return ranking[epoch, rank]


def make_schedule(mix: dict, seconds: float, n_rows: int,
                  rng: np.random.Generator,
                  topics: Optional[np.ndarray] = None) -> Schedule:
    """The requests of one run of ``mix`` over a ``seconds`` window;
    ``topics`` are the corpus's topic bounds (`chipbench.corpus`), if it
    has topics."""
    check_mix(mix, topics is not None)
    if mix["loop"] == OPEN:
        n = max(1, round(mix["rate_rps"] * seconds))
        gaps = rng.permutation(stratified_gaps(n, mix["rate_rps"]))
        due = np.cumsum(gaps)       # sum < n / rate: all due in the window
    else:
        n = int(mix["pool"])
        due = None
    tenant = rng.permutation(np.repeat(
        np.arange(mix["tenants"]),
        zipf_counts(n, mix["tenants"], mix["tenant_zipf"])))
    row = rng.integers(0, n_rows, size=n)
    if "burst_factor" in mix:
        due = burst_warp(due, seconds, *(float(mix[k]) for k in BURST_KEYS))
    if "topic_zipf" in mix:
        topic = topic_of(mix, due, n, len(topics) - 1, rng)
        row = rng.integers(topics[topic], topics[topic + 1])
    return Schedule(due_s=due, tenant=tenant, row=row)


@dataclasses.dataclass
class Log:
    """Per-request timestamps (monotonic seconds) and outcomes of a run.
    NaN marks a time that never came."""
    t0: float
    t_end: float
    due: np.ndarray
    submit: np.ndarray
    done: np.ndarray
    ok: np.ndarray
    results: dict             # request index -> ServeResult
    rid: dict                 # request id -> request index
    t_stop: float = math.nan  # when the harness stopped waiting
    gave_up: bool = False     # stopped with requests still outstanding
    longest_step_s: float = 0.0   # the engine's longest step() call

    @classmethod
    def empty(cls, n: int, t0: float, seconds: float) -> "Log":
        nan = np.full(n, np.nan)
        return cls(t0=t0, t_end=t0 + seconds, due=nan.copy(),
                   submit=nan.copy(), done=nan.copy(),
                   ok=np.zeros(n, bool), results={}, rid={})

    def attempted(self) -> np.ndarray:
        """Indices of the requests the window is judged on: those due in
        the window (every submitted one in a closed loop)."""
        return np.flatnonzero(self.due < self.t_end)

    def latencies(self, idx: np.ndarray) -> np.ndarray:
        """Due -> result for each of ``idx``; a request that failed, was
        shed or never returned counts as missing every limit: its latency
        is the whole wait the harness gave it."""
        done = np.where(self.ok[idx], self.done[idx], self.t_stop)
        return done - self.due[idx]


def drive_open(engine, sched: Schedule, submit_one: Callable[[int], int],
               seconds: float, *, clock=time.monotonic,
               sleep=time.sleep, grace_s: Optional[float] = None) -> Log:
    """Play an open-loop schedule: a submitter thread sends request i at
    its due time, whatever the engine is doing; this thread steps the
    engine and stamps each result as it comes back.  Returns once every
    submitted request has a result, or ``grace_s`` after the window."""
    grace_s = GRACE_S if grace_s is None else grace_s
    n = len(sched)
    t0 = clock()
    log = Log.empty(n, t0, seconds)
    log.due[:] = t0 + sched.due_s
    lock = threading.Lock()
    errors: List[BaseException] = []

    def submitter() -> None:
        try:
            for i in range(n):
                wait = log.due[i] - clock()
                if wait > 0:
                    sleep(wait)
                with lock:
                    log.rid[submit_one(i)] = i
                    log.submit[i] = clock()
        except BaseException as e:       # noqa: BLE001 — re-raised below
            errors.append(e)

    th = threading.Thread(target=submitter, name="loadgen", daemon=True)
    th.start()
    _step_until(engine, log, lock, lambda: not th.is_alive(),
                t0 + seconds + grace_s, clock, sleep)
    th.join(timeout=grace_s)
    if errors:
        raise errors[0]
    return log


def drive_closed(engine, sched: Schedule, submit_one: Callable[[int], int],
                 seconds: float, clients: int, *, clock=time.monotonic,
                 sleep=time.sleep, grace_s: Optional[float] = None) -> Log:
    """Play a closed loop: ``clients`` clients each send their next request
    (the next one of the schedule's pool) as soon as their last result
    returns, with no think time, until the window closes."""
    grace_s = GRACE_S if grace_s is None else grace_s
    n = len(sched)
    t0 = clock()
    log = Log.empty(n, t0, seconds)
    lock = threading.Lock()
    nxt = 0

    def send() -> None:
        nonlocal nxt
        if nxt >= n:
            raise RuntimeError(f"closed-loop pool of {n} requests ran out")
        i = nxt
        nxt += 1
        now = clock()
        log.due[i] = log.submit[i] = now
        log.rid[submit_one(i)] = i

    for _ in range(clients):
        send()
    _step_until(engine, log, lock, lambda: True, t0 + seconds + grace_s,
                clock, sleep, on_result=lambda now: (
                    send() if now < log.t_end else None))
    used = np.zeros(n, bool)
    used[:nxt] = True
    log.due[~used] = np.inf      # never sent: not attempted
    return log


def _step_until(engine, log: Log, lock, sources_done: Callable[[], bool],
                deadline: float, clock, sleep, on_result=None) -> None:
    while True:
        t_step = clock()
        res = engine.step()
        now = clock()
        log.longest_step_s = max(log.longest_step_s, now - t_step)
        if res:
            with lock:
                for r in res:
                    i = log.rid[r.request_id]
                    log.done[i] = now
                    log.ok[i] = r.ok
                    log.results[i] = r
            if on_result is not None:
                for _ in res:
                    on_result(now)
        with lock:
            outstanding = len(log.rid) - len(log.results)
        if sources_done() and outstanding == 0:
            log.t_stop = now
            return
        if now > deadline:
            log.t_stop, log.gave_up = now, True
            return
        if not res:
            sleep(IDLE_SLEEP_S)


def backlog_halves(log: Log, step_s: float = 0.01) -> tuple:
    """The backlog (requests due and not yet answered), averaged over time
    in the first and in the second half of the window."""
    idx = log.attempted()
    due = np.sort(log.due[idx])
    done = np.sort(np.nan_to_num(log.done[idx], nan=np.inf))
    t = np.arange(log.t0, log.t_end, step_s)
    backlog = (np.searchsorted(due, t, "right")
               - np.searchsorted(done, t, "right"))
    half = len(t) // 2
    return float(backlog[:half].mean()), float(backlog[half:].mean())


# The knee rule.  A rate is sustained when, pooled over its repeats, the
# time-averaged backlog of the windows' second halves is at most GROWTH
# times that of their first halves plus SLACK requests: a queue that holds
# steady reads about 1, one that grows by d requests a second over a
# window of W seconds reads (base + 3dW/4) / (base + dW/4).  The knee is
# the highest rate of an ascending sweep below its first rate that is not
# sustained; the cells run at LOAD times it.
GROWTH, SLACK, LOAD = 1.5, 1.0, 0.8


def sustained(halves) -> bool:
    """Whether the backlog halves ``[(first, second), ...]`` of a rate's
    repeats hold steady by the knee rule."""
    first = float(np.mean([h[0] for h in halves]))
    second = float(np.mean([h[1] for h in halves]))
    return second <= GROWTH * first + SLACK


def knee(rates, held) -> Optional[float]:
    """The highest of the ascending ``rates`` below the first one not
    ``held`` (every rate when all are); None when the first is not."""
    best = None
    for rate, ok in zip(rates, held):
        if not ok:
            break
        best = rate
    return best


def rate_summary(log: Log) -> dict:
    """How one window of a sweep went: offered, completed in the window,
    and the time-averaged backlog of each half."""
    idx = log.attempted()
    done_in = np.sum(log.ok[idx] & (log.done[idx] <= log.t_end))
    first, second = backlog_halves(log)
    return {"offered": int(len(idx)), "completed_in_window": int(done_in),
            "backlog_first_half": first, "backlog_second_half": second,
            "seconds": float(log.t_end - log.t0), "gave_up": log.gave_up}
