"""repro.serve engine: batched == sequential parity, micro-batch triggers,
plan cache, metrics accounting."""

import math
import threading
import time

import numpy as np
import pytest

import jax

from repro import obs
from repro.crypto import rlwe
from repro.data import synth
from repro.retrieval.index import FlatIndex
from repro.serve import EngineConfig, ServeEngine
from repro.serve.session import PlanCache, SessionManager

N_DOCS, DIM, K = 1500, 64, 4
N_REQ = 8
TENANTS = ("alice", "bob", "carol")
# small ring keeps the CPU NTTs fast; semantics identical to the default
PARAMS = rlwe.RlweParams(n_poly=1024, chunk=512)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    emb = synth.uniform_corpus(rng, N_DOCS, DIM)
    docs = [f"passage-{i}".encode() for i in range(N_DOCS)]
    index = FlatIndex.build(emb, documents=docs)
    queries = synth.queries_near_corpus(rng, emb, N_REQ)
    return index, emb, queries


def _build(index, *, sequential, max_batch, clock=None, backend="rlwe",
           **config_kw):
    kw = {"clock": clock} if clock is not None else {}
    eng = ServeEngine(
        index,
        config=EngineConfig(max_batch=max_batch, max_wait_s=30.0,
                            sequential=sequential, **config_kw),
        sessions=SessionManager(rlwe_params=PARAMS,
                                deterministic_seeds=True), **kw)
    session_kw = {"paillier_bits": 256} if backend == "paillier" else {}
    for t in TENANTS:
        eng.open_session(t, n=DIM, N=N_DOCS, k=K, radius=0.05,
                         backend=backend, **session_kw)
    return eng


def _run(index, queries, *, sequential, max_batch, **config_kw):
    eng = _build(index, sequential=sequential, max_batch=max_batch,
                 **config_kw)
    for i, q in enumerate(queries):
        eng.submit(TENANTS[i % len(TENANTS)], q, key=jax.random.PRNGKey(i))
    return eng, eng.drain()


def test_batched_matches_sequential_across_batch_sizes(corpus):
    """Same docs / ids / wire bytes at batch sizes 1, 3, 8 as the sequential
    run_remoterag path — the batched crypto is bit-compatible."""
    index, emb, queries = corpus
    _, seq = _run(index, queries, sequential=True, max_batch=1)
    assert [r.batch_size for r in seq] == [1] * N_REQ
    for max_batch in (1, 3, 8):
        eng, got = _run(index, queries, sequential=False,
                        max_batch=max_batch)
        assert len(got) == N_REQ
        assert max(r.batch_size for r in got) == min(max_batch, N_REQ)
        for rs, rb in zip(seq, got):
            assert rs.request_id == rb.request_id
            assert rs.ids.tolist() == rb.ids.tolist()
            assert rs.docs == rb.docs
            assert (rs.transcript.total_bytes
                    == rb.transcript.total_bytes)
            assert (rs.transcript.request_bytes
                    == rb.transcript.request_bytes)
            assert rs.transcript.reply_bytes == rb.transcript.reply_bytes


def test_batched_results_match_plaintext_oracle(corpus):
    index, emb, queries = corpus
    _, got = _run(index, queries, sequential=False, max_batch=8)
    for res in got:
        q = queries[res.request_id]
        oracle = np.argsort(-(emb @ q), kind="stable")[:K]
        assert set(res.ids.tolist()) == set(oracle.tolist())
        assert res.docs == [f"passage-{i}".encode() for i in res.ids]


def test_plan_cache_hits_for_repeat_tenants():
    cache = PlanCache()
    mgr = SessionManager(rlwe_params=PARAMS, plan_cache=cache)
    a = mgr.open("a", n=DIM, N=N_DOCS, k=K, radius=0.05)
    assert (cache.hits, cache.misses) == (0, 1)
    b = mgr.open("b", n=DIM, N=N_DOCS, k=K, radius=0.05)
    assert (cache.hits, cache.misses) == (1, 1)
    assert a.plan is b.plan          # cached object reused, no re-planning
    assert a.user.sk is not b.user.sk  # but keys stay per-tenant
    mgr.open("c", n=DIM, N=N_DOCS, k=K, radius=0.09)
    assert cache.misses == 2         # different knobs -> new plan
    # re-opening an existing tenant with identical knobs is a no-op ...
    assert mgr.open("a", n=DIM, N=N_DOCS, k=K, radius=0.05) is a
    # ... but changing the knobs of a live session is an error
    with pytest.raises(ValueError, match="different knobs"):
        mgr.open("a", n=DIM, N=N_DOCS, k=K, radius=0.09)


def test_paillier_batched_matches_sequential(corpus):
    """The paillier backend rides the same staged pipeline through the
    crypto-backend seam (vectorized RNS crypto on the batched path, the
    object path sequentially); parity must hold down to the wire bytes,
    incl. deterministic keygen."""
    index, emb, queries = corpus

    def run(sequential):
        eng = ServeEngine(
            index,
            config=EngineConfig(max_batch=4, max_wait_s=30.0,
                                sequential=sequential),
            sessions=SessionManager(rlwe_params=PARAMS,
                                    deterministic_seeds=True))
        for t in TENANTS[:2]:
            eng.open_session(t, n=DIM, N=N_DOCS, k=K, radius=0.05,
                             backend="paillier", paillier_bits=256)
        for i in range(4):
            eng.submit(TENANTS[i % 2], queries[i], key=jax.random.PRNGKey(i))
        return eng.drain()

    seq, got = run(True), run(False)
    assert [r.batch_size for r in got] == [4] * 4
    for rs, rb in zip(seq, got):
        assert rs.ids.tolist() == rb.ids.tolist()
        assert rs.docs == rb.docs
        assert rs.transcript.total_bytes == rb.transcript.total_bytes


def test_size_and_deadline_triggers(corpus):
    index, _, queries = corpus
    now = [0.0]
    eng = _build(index, sequential=False, max_batch=3,
                 clock=lambda: now[0])
    eng.config = EngineConfig(max_batch=3, max_wait_s=5.0, sequential=False)
    eng.submit("alice", queries[0], key=jax.random.PRNGKey(0))
    eng.submit("bob", queries[1], key=jax.random.PRNGKey(1))
    assert eng.step() == []          # neither trigger fired
    assert eng.pending == 2
    eng.submit("carol", queries[2], key=jax.random.PRNGKey(2))
    out = eng.step()                 # size trigger: 3 == max_batch
    assert len(out) == 3 and eng.pending == 0
    eng.submit("alice", queries[3], key=jax.random.PRNGKey(3))
    assert eng.step() == []
    now[0] += 6.0                    # age past the deadline
    out = eng.step()
    assert len(out) == 1 and out[0].batch_size == 1


def test_metrics_accounting(corpus):
    index, _, queries = corpus
    eng, got = _run(index, queries, sequential=False, max_batch=8)
    summary = eng.metrics.summary()
    agg = summary["aggregate"]
    assert agg["count"] == N_REQ
    assert set(summary["tenants"]) == set(TENANTS)
    per_tenant = sum(s["count"] for s in summary["tenants"].values())
    assert per_tenant == N_REQ
    want_wire = sum(r.transcript.total_bytes for r in got)
    assert eng.metrics.aggregate.total_wire_bytes == want_wire
    assert agg["p99_latency_s"] >= agg["p50_latency_s"] >= 0
    assert "failures" not in summary         # clean run: no failure block


def test_submit_without_session_raises_keyerror(corpus):
    """A missing session is a real error, not an assert (`python -O`
    strips asserts, which would turn this into silent mis-batching)."""
    index, _, queries = corpus
    eng = _build(index, sequential=False, max_batch=2)
    with pytest.raises(KeyError, match="nobody"):
        eng.submit("nobody", queries[0])
    # a (1, n) embedding would group with (n,) requests (the key uses the
    # last axis) and then blow up the batch stack mid-dispatch — rejected
    # at submit instead
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(TENANTS[0], queries[0][None, :])


class _FaultyFetch:
    """Fault-injecting cloud seam: `handle_fetch` raises the first
    ``fail_times`` calls, then delegates — the failure lands mid-dispatch,
    after the crypto, exactly where a lost batch would hurt most."""

    def __init__(self, cloud, fail_times):
        self.cloud = cloud
        self.remaining = fail_times
        self.calls = 0

    def __call__(self, cand_ids, msg):
        self.calls += 1
        if self.remaining:
            self.remaining -= 1
            raise RuntimeError("injected cloud fault")
        return type(self.cloud).handle_fetch(self.cloud, cand_ids, msg)


class _PoisonIds:
    """Persistently poison ONE lane: raise whenever the fetch resolves to
    the poisoned request's result ids (its batched lane *and* its solo
    quarantine retry fail; every other lane's fetch delegates)."""

    def __init__(self, cloud, poison_ids):
        self.cloud = cloud
        self.poison_ids = list(poison_ids)

    def __call__(self, cand_ids, msg):
        ids = [int(cand_ids[p]) for p in msg.positions]
        if ids == self.poison_ids:
            raise RuntimeError("persistently poisoned lane")
        return type(self.cloud).handle_fetch(self.cloud, cand_ids, msg)


def test_single_poisoned_lane_in_full_batch(corpus):
    """One persistently poisoned lane in a batch of 8: exactly that request
    errors, the other 7 succeed bit-identically to the sequential path, no
    healthy lane is encrypted twice, and the metrics record exactly one
    batch (no phantom or duplicate batches)."""
    index, _, queries = corpus
    _, want = _run(index, queries, sequential=True, max_batch=1)
    # distinct result sets per request, so ids identify the poisoned lane
    assert len({tuple(r.ids.tolist()) for r in want}) == N_REQ
    eng = _build(index, sequential=False, max_batch=8)
    eng.cloud.handle_fetch = _PoisonIds(eng.cloud, want[0].ids.tolist())
    for i, q in enumerate(queries):
        eng.submit(TENANTS[i % len(TENANTS)], q, key=jax.random.PRNGKey(i))
    got = eng.drain()
    assert len(got) == N_REQ
    bad = [r for r in got if not r.ok]
    assert [r.request_id for r in bad] == [0]
    assert "persistently poisoned lane" in bad[0].error
    assert bad[0].quarantined and bad[0].docs == [] and bad[0].ids.size == 0
    for rs, rb in zip(want[1:], got[1:]):
        assert rb.ok and not rb.quarantined
        assert rs.request_id == rb.request_id
        assert rs.ids.tolist() == rb.ids.tolist()
        assert rs.docs == rb.docs
        assert rs.transcript.total_bytes == rb.transcript.total_bytes
        assert rs.transcript.request_bytes == rb.transcript.request_bytes
        assert rs.transcript.reply_bytes == rb.transcript.reply_bytes
    m = eng.metrics
    assert m.num_batches == 1 and list(m.dispatch_sizes) == [N_REQ]
    assert m.failed_dispatches == 0
    assert m.quarantined_lanes == 1 and m.retried_requests == 1
    assert m.quarantined_retry_ok == 0 and m.error_results == 1
    # 8 batched lane encryptions + 1 solo-retry encryption; the 7 healthy
    # lanes were each encrypted exactly once
    assert m.lane_encryptions == N_REQ + 1
    assert m.healthy_reencryptions == 0
    assert m.aggregate.count == N_REQ - 1       # healthy lanes, once each
    # occupancy counts *completed* lanes: the quarantined one is lost fill
    assert m.dispatch_lanes == N_REQ - 1
    assert m.occupancy(N_REQ) == (N_REQ - 1) / N_REQ
    assert eng.pending == 0


def test_paillier_poisoned_lane_isolated_like_rlwe(corpus):
    """Fault isolation is backend-neutral through the crypto seam: one
    persistently poisoned lane in a paillier batch of 8 errors alone,
    its 7 batchmates complete bit-identically to the sequential path, no
    healthy lane is re-encrypted — exactly the rlwe contract."""
    index, _, queries = corpus
    _, want = _run(index, queries, sequential=True, max_batch=1,
                   backend="paillier")
    eng = _build(index, sequential=False, max_batch=8, backend="paillier")
    eng.cloud.handle_fetch = _PoisonIds(eng.cloud, want[0].ids.tolist())
    for i, q in enumerate(queries):
        eng.submit(TENANTS[i % len(TENANTS)], q, key=jax.random.PRNGKey(i))
    got = eng.drain()
    assert len(got) == N_REQ
    bad = [r for r in got if not r.ok]
    assert [r.request_id for r in bad] == [0]
    assert bad[0].quarantined
    for rs, rb in zip(want[1:], got[1:]):
        assert rb.ok and not rb.quarantined
        assert rs.ids.tolist() == rb.ids.tolist()
        assert rs.docs == rb.docs
        assert rs.transcript.total_bytes == rb.transcript.total_bytes
    m = eng.metrics
    assert m.quarantined_lanes == 1 and m.error_results == 1
    assert m.lane_encryptions == N_REQ + 1
    assert m.healthy_reencryptions == 0


def test_paillier_traced_run_covers_same_stages(corpus):
    """Tracing is backend-neutral through the crypto seam: a traced
    paillier batch emits the same core stage spans as rlwe, the score
    spans carry backend="paillier", and tracing changes nothing."""
    index, _, queries = corpus
    _, base = _run(index, queries, sequential=False, max_batch=8,
                   backend="paillier")
    eng, got = _run(index, queries, sequential=False, max_batch=8,
                    backend="paillier", trace=True)
    assert len(got) == N_REQ and all(r.ok for r in got)
    for rb, rt in zip(base, got):
        assert rb.ids.tolist() == rt.ids.tolist()
        assert rb.transcript.total_bytes == rt.transcript.total_bytes
    spans = eng.tracer.spans()
    names = {s.name for s in spans}
    assert {"queue_wait", "dispatch", "perturb", "topk", "encrypt",
            "score", "decrypt", "finish"} <= names
    score_spans = [s for s in spans if s.name == "score"]
    assert score_spans
    assert all(s.attrs.get("backend") == "paillier" for s in score_spans)


def test_poison_that_disappears_on_retry(corpus):
    """A transient lane fault quarantines only that lane: its batchmates
    complete from their already-computed state (never re-encrypted), the
    quarantined lane heals on its solo retry and is recorded exactly once,
    with latency measured from the original submit."""
    index, _, queries = corpus
    _, want = _run(index, queries, sequential=False, max_batch=8)
    eng = _build(index, sequential=False, max_batch=8)
    eng.cloud.handle_fetch = _FaultyFetch(eng.cloud, fail_times=1)
    for i, q in enumerate(queries):
        eng.submit(TENANTS[i % len(TENANTS)], q, key=jax.random.PRNGKey(i))
    got = eng.drain()
    assert len(got) == N_REQ and all(r.ok for r in got)
    healed = [r for r in got if r.quarantined]
    assert [r.request_id for r in healed] == [0]
    for rs, rb in zip(want, got):
        assert rs.request_id == rb.request_id
        assert rs.ids.tolist() == rb.ids.tolist()
        assert rs.docs == rb.docs
    m = eng.metrics
    # one real batch; the solo retry is not a batch of its own, and the
    # quarantined lane is not counted as completed in-batch fill
    assert m.num_batches == 1 and list(m.dispatch_sizes) == [N_REQ]
    assert m.dispatch_lanes == N_REQ - 1
    assert m.failed_dispatches == 0
    assert m.quarantined_lanes == 1 and m.retried_requests == 1
    assert m.quarantined_retry_ok == 1 and m.error_results == 0
    # recorded once per request — no double count for the healed lane
    assert m.aggregate.count == N_REQ
    assert m.healthy_reencryptions == 0
    assert m.lane_encryptions == N_REQ + 1      # only the healed lane twice
    summary = eng.metrics.summary()
    healed_tenant = summary["tenants"][healed[0].tenant]
    assert healed_tenant["quarantined_retry_ok"] == 1
    assert "errors" not in healed_tenant        # healed != terminal error
    assert eng.pending == 0


def test_dispatch_failure_after_retries_returns_error_results(corpus):
    """When the cloud keeps failing for every lane, drain() still
    terminates and hands every request back as an error result — zero
    requests lost, zero phantom batches recorded."""
    index, _, queries = corpus
    eng = _build(index, sequential=False, max_batch=3)
    eng.cloud.handle_fetch = _FaultyFetch(eng.cloud, fail_times=10**9)
    rids = [eng.submit(TENANTS[i], queries[i], key=jax.random.PRNGKey(i))
            for i in range(3)]
    got = eng.drain()
    assert [r.request_id for r in got] == rids
    assert all(not r.ok for r in got)
    assert all("injected cloud fault" in r.error for r in got)
    assert all(r.docs == [] and r.ids.size == 0 and r.transcript is None
               for r in got)
    assert eng.pending == 0
    assert eng.metrics.num_batches == 0      # no phantom batches
    assert eng.metrics.failed_dispatches == 1    # all lanes quarantined
    assert eng.metrics.quarantined_lanes == 3
    assert eng.metrics.retried_requests == 3     # one solo retry each
    assert eng.metrics.quarantined_retry_ok == 0
    summary = eng.metrics.summary()
    assert summary["failures"]["error_results"] == 3
    assert eng.metrics.aggregate.errors == 3
    # error-only tenants have no latency samples — their summaries (and the
    # aggregate's) must degrade gracefully, not crash on an empty window
    assert summary["aggregate"] == {"count": 0, "errors": 3}
    for t in TENANTS:
        assert summary["tenants"][t] == {"count": 0, "errors": 1}
    # the engine stays healthy: un-fault the cloud and serve again
    eng.cloud.handle_fetch = _FaultyFetch(eng.cloud, fail_times=0)
    eng.submit(TENANTS[0], queries[0], key=jax.random.PRNGKey(0))
    ok = eng.drain()
    assert len(ok) == 1 and ok[0].ok


def test_batched_stage_fault_is_bisected_to_one_lane(corpus, monkeypatch):
    """A fault inside a *batched* stage (here: the vmapped DistanceDP
    perturbation) is attributed by bisection to the one offending lane:
    its batchmates survive the same dispatch, and the quarantined lane
    heals on the solo sequential retry (which does not use the batched
    seam).  The poisoned lane never reached encryption, so no healthy
    crypto is wasted."""
    from repro.serve import batching as batching_mod

    index, _, queries = corpus
    poison_q = np.asarray(queries[2], np.float32)
    real = batching_mod.perturb_batch

    def poisoned(keys, E, epss):
        if any(np.array_equal(row, poison_q) for row in np.asarray(E)):
            raise RuntimeError("poisoned batched stage")
        return real(keys, E, epss)

    monkeypatch.setattr(batching_mod, "perturb_batch", poisoned)
    _, want = _run(index, queries, sequential=True, max_batch=1)
    eng = _build(index, sequential=False, max_batch=8)
    for i, q in enumerate(queries):
        eng.submit(TENANTS[i % len(TENANTS)], q, key=jax.random.PRNGKey(i))
    got = eng.drain()
    assert len(got) == N_REQ and all(r.ok for r in got)
    assert [r.request_id for r in got if r.quarantined] == [2]
    for rs, rb in zip(want, got):
        assert rs.ids.tolist() == rb.ids.tolist()
        assert rs.docs == rb.docs
    m = eng.metrics
    assert m.quarantined_lanes == 1 and m.quarantined_retry_ok == 1
    # 7 healthy batched encryptions + 1 solo-retry encryption; the
    # quarantined lane was dropped before the encrypt stage
    assert m.lane_encryptions == N_REQ
    assert m.healthy_reencryptions == 0
    assert m.num_batches == 1 and list(m.dispatch_sizes) == [N_REQ]


def test_batch_only_heisenbug_heals_without_quarantine(corpus, monkeypatch):
    """A fault that only manifests on multi-lane invocations (a batch-only
    heisenbug) bisects down to singleton re-runs that all succeed: every
    lane completes, nothing is quarantined, and — because the fault sat in
    a pre-encryption stage — no query is encrypted twice."""
    from repro.serve import batching as batching_mod

    index, _, queries = corpus
    real = batching_mod.topk_batch

    def flaky(index_, pert, kprime, *, use_pallas=None, nprobe=None):
        if np.shape(pert)[0] > 1:
            raise RuntimeError("batch-only fault")
        return real(index_, pert, kprime, use_pallas=use_pallas,
                    nprobe=nprobe)

    monkeypatch.setattr(batching_mod, "topk_batch", flaky)
    _, want = _run(index, queries, sequential=True, max_batch=1)
    eng = _build(index, sequential=False, max_batch=8)
    for i, q in enumerate(queries):
        eng.submit(TENANTS[i % len(TENANTS)], q, key=jax.random.PRNGKey(i))
    got = eng.drain()
    assert len(got) == N_REQ and all(r.ok for r in got)
    assert not any(r.quarantined for r in got)
    for rs, rb in zip(want, got):
        assert rs.ids.tolist() == rb.ids.tolist()
        assert rs.docs == rb.docs
    m = eng.metrics
    assert m.quarantined_lanes == 0 and m.error_results == 0
    assert m.lane_encryptions == N_REQ and m.healthy_reencryptions == 0


def test_sequential_dispatch_isolates_poisoned_lane(corpus):
    """On the sequential comparison path a single poisoned request must not
    sink its batchmates: healthy lanes complete, the poisoned one errors
    after its solo quarantine retry."""
    index, _, queries = corpus
    eng = _build(index, sequential=True, max_batch=3)
    # fail exactly the 2nd request and its retry: lane order is r0(1),
    # r1(2, fails), r2(3) — the lane loop continues past the failure —
    # then the quarantined r1 retries solo as call 4 and fails for good
    calls = [0]

    def poisoned(cand_ids, msg):
        calls[0] += 1
        if calls[0] in (2, 4):
            raise RuntimeError("poisoned lane")
        return type(eng.cloud).handle_fetch(eng.cloud, cand_ids, msg)
    eng.cloud.handle_fetch = poisoned
    for i in range(3):
        eng.submit(TENANTS[i], queries[i], key=jax.random.PRNGKey(i))
    got = eng.drain()
    assert len(got) == 3
    oks = [r for r in got if r.ok]
    bad = [r for r in got if not r.ok]
    assert len(oks) == 2 and len(bad) == 1
    assert "poisoned lane" in bad[0].error and bad[0].quarantined


def _refill_engine(index, clock, *, max_batch=3, max_wait_s=5.0):
    eng = ServeEngine(
        index,
        config=EngineConfig(max_batch=max_batch, max_wait_s=max_wait_s,
                            sequential=False),
        sessions=SessionManager(rlwe_params=PARAMS,
                                deterministic_seeds=True), clock=clock)
    for t in TENANTS:
        eng.open_session(t, n=DIM, N=N_DOCS, k=K, radius=0.05,
                         backend="rlwe")
    return eng


def test_refill_admits_compatible_request_immediately(corpus):
    """A group whose batch dispatched under max_batch holds a refill
    credit: a compatible request arriving within the batching window is
    dispatched by the next step() immediately, without aging out
    max_wait_s again.  The credit expires after one window."""
    index, _, queries = corpus
    now = [0.0]
    eng = _refill_engine(index, lambda: now[0])
    eng.submit("alice", queries[0], key=jax.random.PRNGKey(0))
    eng.submit("bob", queries[1], key=jax.random.PRNGKey(1))
    assert eng.step() == []              # neither trigger fired
    now[0] = 5.0                         # deadline: partial batch of 2 < 3
    assert len(eng.step()) == 2
    # refill: a compatible late arrival does not wait out a new deadline
    eng.submit("carol", queries[2], key=jax.random.PRNGKey(2))
    now[0] = 5.001
    out = eng.step()
    assert len(out) == 1 and out[0].ok
    assert eng.metrics.refill_dispatches == 1
    assert eng.metrics.refilled_requests == 1
    # a refill dispatch must not re-grant the credit (it would self-renew
    # and the group would never form a real batch again): the next arrival
    # is back to normal size/deadline batching
    now[0] = 5.002
    eng.submit("alice", queries[3], key=jax.random.PRNGKey(3))
    assert eng.step() == []              # no credit: back to batching
    assert eng.metrics.refill_dispatches == 1
    now[0] = 10.002                      # its own deadline fires normally
    assert len(eng.step()) == 1
    assert eng.metrics.refill_dispatches == 1
    # ... and a deadline-granted credit expires after one batching window
    now[0] = 15.2                        # credit from 10.002 expired at
    eng.submit("bob", queries[4], key=jax.random.PRNGKey(4))
    assert eng.step() == []              # 15.002; request age is only 0
    assert eng.metrics.refill_dispatches == 1
    now[0] = 20.2
    assert len(eng.step()) == 1          # deadline again
    assert eng.metrics.summary()["refills"]["refill_dispatches"] == 1


def test_refill_serves_burst_tail(corpus):
    """A full size-triggered dispatch that leaves requests queued grants a
    credit too: the burst tail rides the next step() instead of waiting
    out the deadline (and the refill dispatch does not re-grant)."""
    index, _, queries = corpus
    now = [0.0]
    eng = _refill_engine(index, lambda: now[0])
    for i in range(4):
        eng.submit(TENANTS[i % 3], queries[i], key=jax.random.PRNGKey(i))
    assert len(eng.step()) == 3          # size trigger: 3 of the 4
    now[0] = 0.001
    out = eng.step()                     # tail of 1 rides the credit
    assert len(out) == 1 and out[0].ok
    assert eng.metrics.refill_dispatches == 1
    assert eng.metrics.refilled_requests == 1
    now[0] = 0.002                       # no self-renewal from the refill
    eng.submit("alice", queries[4], key=jax.random.PRNGKey(4))
    assert eng.step() == []


def test_refill_ignores_incompatible_group(corpus):
    """A refill credit belongs to the (backend, n, k') group that earned
    it: an incompatible request (paillier backend here, so a different
    group key) must wait out its own triggers."""
    index, _, queries = corpus
    now = [0.0]
    eng = _refill_engine(index, lambda: now[0])
    eng.open_session("dora", n=DIM, N=N_DOCS, k=K, radius=0.05,
                     backend="paillier", paillier_bits=256)
    eng.submit("alice", queries[0], key=jax.random.PRNGKey(0))
    now[0] = 5.0
    assert len(eng.step()) == 1          # partial dispatch -> rlwe credit
    # incompatible arrival: different (backend, n, k') group, no credit
    eng.submit("dora", queries[1], key=jax.random.PRNGKey(1))
    now[0] = 5.001
    assert eng.step() == []              # must not ride the rlwe credit
    assert eng.metrics.refill_dispatches == 0
    now[0] = 5.001 + 5.0                 # its own deadline
    out = eng.step()
    assert len(out) == 1 and out[0].ok


def test_close_drains_and_stops_admitter(corpus):
    """`close()` (and the context manager) drains pending work, stops the
    sharded cache's background admitter thread, and rejects further
    submissions; close is idempotent."""
    index, _, queries = corpus
    cfg = EngineConfig(
        max_batch=4, max_wait_s=30.0,
        cache_config=rlwe.CandidateCacheConfig(num_shards=4))
    with ServeEngine(index, config=cfg,
                     sessions=SessionManager(
                         rlwe_params=PARAMS,
                         deterministic_seeds=True)) as eng:
        for t in TENANTS:
            eng.open_session(t, n=DIM, N=N_DOCS, k=K, radius=0.05,
                             backend="rlwe")
        for i in range(3):
            eng.submit(TENANTS[i], queries[i], key=jax.random.PRNGKey(i))
        out = eng.close()                # drains the queued requests
        assert len(out) == 3 and all(r.ok for r in out)
        cache = eng.cloud.index.peek_candidate_cache(
            eng.cloud.rlwe_params, eng.cloud.cache_config)
        assert isinstance(cache, rlwe.ShardedCandidateCache)
        worker = cache._worker
        assert worker is None or not worker.is_alive()
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(TENANTS[0], queries[0])
        assert eng.close() == []         # idempotent
    # __exit__ re-closes (a no-op); the engine object stays inspectable
    assert eng.metrics.aggregate.count == 3


def test_metrics_window_bounded():
    """Latency/batch samples are windowed (no unbounded growth under the
    million-user north star) while counts and byte totals stay exact."""
    from repro.core.protocol import ProtocolTranscript
    from repro.serve.metrics import ServeMetrics

    m = ServeMetrics(window=4)
    tr = ProtocolTranscript(plan=None, path="direct", request_bytes=10,
                            reply_bytes=5, fetch_bytes=1, docs_bytes=2,
                            ot_wire_bytes=0)
    for i in range(10):
        m.record("t", latency_s=float(i), batch_size=2, transcript=tr)
        m.record_batch(2)
    agg = m.aggregate
    assert agg.count == 10                       # exact total
    assert agg.total_wire_bytes == 10 * 18       # exact total
    assert len(agg.latencies_s) == 4             # bounded window
    assert list(agg.latencies_s) == [6.0, 7.0, 8.0, 9.0]
    assert agg.percentile(50) == 7.5             # over the window
    assert m.num_batches == 10 and len(m.dispatch_sizes) == 4
    assert m.summary()["aggregate"]["count"] == 10
    with pytest.raises(ValueError, match="window"):
        ServeMetrics(window=0).record("t", latency_s=0.0, batch_size=1,
                                      transcript=tr)


def test_tenant_percentile_nan_on_empty_window():
    """An error-only (or untouched) tenant has no latency samples;
    percentile must read as NaN, never an opaque numpy error."""
    from repro.serve.metrics import ServeMetrics, TenantStats

    stats = TenantStats(window=4)
    assert math.isnan(stats.percentile(50))
    assert math.isnan(stats.percentile(99))
    assert stats.summary() == {"count": 0}
    # the summary of an error-only tenant includes the error count but
    # never calls percentile on the empty window
    m = ServeMetrics()
    m.record_error("ghost")
    summ = m.summary()
    assert summ["tenants"]["ghost"] == {"count": 0, "errors": 1}
    assert math.isnan(m.aggregate.percentile(50))


def test_summary_always_surfaces_healthy_reencryptions():
    """healthy_reencryptions is the CI-gated isolation contract: a nonzero
    value must surface in summary() even when every other failure counter
    is zero (a healthy-looking run that silently re-encrypted would
    otherwise hide its contract breach)."""
    from repro.serve.metrics import ServeMetrics

    m = ServeMetrics()
    assert "failures" not in m.summary()         # clean run stays compact
    m.record_healthy_reencryptions(2)
    failures = m.summary()["failures"]
    assert failures["healthy_reencryptions"] == 2
    assert failures["quarantined_lanes"] == 0    # the only nonzero trigger


def test_metrics_occupancy_and_window_edges():
    from repro.core.protocol import ProtocolTranscript
    from repro.serve.metrics import ServeMetrics

    m = ServeMetrics()
    assert m.occupancy(8) is None                # no batches yet
    m.record_batch(8, completed=5)               # 3 lanes quarantined away
    assert m.occupancy(8) == pytest.approx(5 / 8)
    m.record_batch(8)                            # full batch, all completed
    assert m.occupancy(8) == pytest.approx(13 / 16)
    assert m.occupancy(0) is None                # degenerate max_batch

    # window=1 is the tightest legal window: every sample evicts the last
    tr = ProtocolTranscript(plan=None, path="direct", request_bytes=10,
                            reply_bytes=5, fetch_bytes=1, docs_bytes=2,
                            ot_wire_bytes=0)
    m1 = ServeMetrics(window=1)
    for i in range(3):
        m1.record("t", latency_s=float(i), batch_size=1, transcript=tr)
    agg = m1.aggregate
    assert list(agg.latencies_s) == [2.0]
    assert agg.percentile(50) == 2.0 and agg.percentile(99) == 2.0
    assert agg.count == 3                        # exact total survives
    assert agg.total_wire_bytes == 3 * 18


def test_tracing_disabled_by_default(corpus):
    """EngineConfig() leaves tracing off: the engine runs on the shared
    NULL tracer, records nothing, and refuses to write an empty trace."""
    index, _, queries = corpus
    eng, got = _run(index, queries, sequential=False, max_batch=8)
    assert all(r.ok for r in got)
    assert eng.tracer is obs.NULL_TRACER
    assert eng.tracer.spans() == []
    assert eng.trace_summary() is None
    assert "trace" not in eng.metrics.summary()
    with pytest.raises(RuntimeError, match="trace"):
        eng.write_trace("/tmp/should-not-exist.json")


def test_traced_run_stages_redaction_reconciliation(corpus, tmp_path):
    """The tentpole end-to-end: a traced batched run (a) stays
    bit-identical to the untraced run, (b) covers every pipeline stage,
    (c) carries only whitelisted scalar attrs on every span (redaction by
    construction over a *real* stream), (d) nests stage spans inside
    their dispatch and reconciles queue_wait + dispatch with each
    request's end-to-end latency, and (e) exports a loadable
    Chrome-trace."""
    index, _, queries = corpus
    _, base = _run(index, queries, sequential=False, max_batch=8)
    eng, got = _run(index, queries, sequential=False, max_batch=8,
                    trace=True)
    assert len(got) == N_REQ and all(r.ok for r in got)
    for rb, rt in zip(base, got):                # (a) tracing changes nothing
        assert rb.ids.tolist() == rt.ids.tolist()
        assert rb.docs == rt.docs
        assert rb.transcript.total_bytes == rt.transcript.total_bytes

    spans = eng.tracer.spans()
    names = {s.name for s in spans}
    assert {"queue_wait", "dispatch", "perturb", "topk", "encrypt",
            "score", "decrypt", "finish"} <= names          # (b)

    for s in spans:                              # (c) redaction contract
        for key, val in s.attrs.items():
            assert key in obs.ALLOWED_ATTR_KEYS
            assert isinstance(val, (bool, int, float, str))
            if isinstance(val, str):
                assert len(val) <= 64

    # (d) timeline consistency: every stage span nests inside its batch's
    # dispatch interval, and queue_wait + dispatch explain each latency
    dispatches = {s.batch_id: s for s in spans if s.name == "dispatch"}
    waits = {s.request_id: s for s in spans if s.name == "queue_wait"}
    eps = 1e-6
    for s in spans:
        if s.name in ("dispatch", "queue_wait") or s.batch_id is None \
                or s.track == "admitter" or s.duration_s == 0.0:
            continue
        d = dispatches[s.batch_id]
        assert d.t_start - eps <= s.t_start
        assert s.t_end <= d.t_end + eps
    assert len(waits) == N_REQ
    for res in got:
        w = waits[res.request_id]
        d = dispatches[w.batch_id]
        assert res.latency_s <= w.duration_s + d.duration_s + 0.05
    # per-batch stage-duration sums can never exceed the dispatch span
    for b, d in dispatches.items():
        stage_sum = sum(s.duration_s for s in spans
                        if s.batch_id == b and s.track == "engine"
                        and s.name in ("perturb", "topk", "score",
                                       "decrypt"))
        assert stage_sum <= d.duration_s + eps

    # summary merge + stage histograms
    summ = eng.metrics.summary()
    assert summ["trace"]["stages"]["dispatch"]["count"] >= 1
    assert eng.trace_summary() == eng.tracer.snapshot()

    path = tmp_path / "serve-trace.json"         # (e) export round-trip
    n_events = eng.write_trace(str(path))
    assert n_events == len(spans)
    doc = obs.load_chrome_trace(str(path))
    assert doc["metadata"]["stage_summary"] == eng.tracer.stage_summary()


@pytest.mark.parametrize("trace", [True, False])
def test_score_span_ends_at_device_readiness(corpus, monkeypatch, trace):
    """Traced, the engine waits for the score ciphertexts inside every
    ``score`` span, so their device time is read as score's and not as
    decrypt's; untraced, it never waits."""
    index, _, queries = corpus
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(time.monotonic())
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    eng, got = _run(index, queries, sequential=False, max_batch=3,
                    trace=trace)
    assert len(got) == N_REQ and all(r.ok for r in got)
    if not trace:
        assert calls == []
        return
    scores = [s for s in eng.tracer.spans() if s.name == "score"]
    assert len(scores) >= 3                 # 8 requests, batches of <= 3
    assert len(calls) == len(scores)
    for s in scores:
        assert sum(s.t_start <= t <= s.t_end for t in calls) == 1


@pytest.mark.parametrize("max_batch", [1, 3, 8])
def test_one_queue_wait_span_per_completed_request(corpus, max_batch):
    """Each request's wait in the queue is one ``queue_wait`` span, from
    its enqueue to the start of its batch's dispatch."""
    index, _, queries = corpus
    eng, got = _run(index, queries, sequential=False, max_batch=max_batch,
                    trace=True)
    assert len(got) == N_REQ and all(r.ok for r in got)
    spans = eng.tracer.spans()
    waits = [s for s in spans if s.name == "queue_wait"]
    assert sorted(s.request_id for s in waits) == sorted(
        r.request_id for r in got)
    dispatches = {s.batch_id: s for s in spans if s.name == "dispatch"}
    assert len(dispatches) == -(-N_REQ // max_batch)
    for w in waits:
        assert w.t_end == pytest.approx(dispatches[w.batch_id].t_start,
                                        abs=1e-3)


def test_sharded_admission_span_parented_and_overlapping_encrypt(corpus):
    """The async shard admitter emits "cache_admit" spans on its own
    "admitter" track, parented (batch_id) to the dispatch that enqueued
    the admission — and the admission copy genuinely overlaps that
    batch's encrypt stage.  The overlap is forced deterministically: the
    admit hook blocks until the first encrypt begins, and the encrypts
    are slowed enough that the copy lands inside one."""
    index, _, queries = corpus
    eng = _build(index, sequential=False, max_batch=8, trace=True,
                 cache_config=rlwe.CandidateCacheConfig(
                     num_shards=8, admit_threshold=1))
    cache = eng.cloud.candidate_cache
    assert isinstance(cache, rlwe.ShardedCandidateCache)
    encrypt_started = threading.Event()
    for t in TENANTS:
        user = eng.sessions.get(t).user
        orig = user.encrypt_query

        def slow_encrypt(emb, _orig=orig):
            encrypt_started.set()
            time.sleep(0.05)        # hold the encrypt span open
            return _orig(emb)

        user.encrypt_query = slow_encrypt
    cache._admit_hook = lambda s: encrypt_started.wait(timeout=10.0)
    try:
        for i, q in enumerate(queries):
            eng.submit(TENANTS[i % len(TENANTS)], q,
                       key=jax.random.PRNGKey(i))
        got = eng.drain()
        assert all(r.ok for r in got)
        cache.flush()
        spans = eng.tracer.spans()
        admits = [s for s in spans
                  if s.name == "cache_admit" and s.track == "admitter"]
        assert admits, "background admitter must emit admission spans"
        dispatch_bids = {s.batch_id for s in spans if s.name == "dispatch"}
        for a in admits:                      # parented to a real dispatch
            assert a.batch_id in dispatch_bids
            assert a.attrs["ok"] is True and a.attrs["bytes"] > 0
        gathers = [s for s in spans if s.name == "cache_gather"]
        assert gathers and all(g.batch_id in dispatch_bids for g in gathers)
        encrypts = [s for s in spans if s.name == "encrypt"]
        assert any(a.t_start < e.t_end and e.t_start < a.t_end
                   for a in admits for e in encrypts), \
            "admission copy must overlap the encrypt stage"
    finally:
        cache._admit_hook = None              # cache is index-memoized
        eng.close()
