"""Sharded HBM-resident candidate cache: sharded on-demand gather must be
bit-identical to the dense cache and to cold per-request packing (batch
1/3/8, both strides, fallback + fused Pallas kernel); the fused-iNTT kernel
must match the staged fallback; LRU eviction / re-pinning must be
deterministic under a fixed access trace (legacy ``async_admission=False``
mode) and must never change the bits.  The admission-policy suite below
pins down the async/frequency-aware path: convergence to the synchronous
resident set, bit-identity while an admission is in flight, the 2nd-touch
rule under one-shot sweeps, counter decay, the bounded admit queue, and the
prefetch touch-credit accounting."""

import threading

import numpy as np
import pytest

from repro.crypto import rlwe
from repro.kernels.ntt import ops as ntt_ops

# n_dim=384 <= chunk -> stride=chunk (2 cands/ct); n_dim=768 > chunk ->
# stride=2*chunk (1 cand/ct, 2 chunks): both packing regimes.
PARAMS = rlwe.RlweParams(n_poly=1024, chunk=512)
NUM_DOCS = 40
KPRIME = 9          # not a multiple of cands_per_ct=2: pad path
SHARD_DOCS = 8      # 5 shards over 40 docs


def _unit(rng, *shape):
    x = rng.normal(size=shape)
    return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(np.float32)


@pytest.fixture(scope="module")
def sk():
    return rlwe.keygen(PARAMS, np.random.default_rng(0))


@pytest.fixture(scope="module", params=[384, 768])
def setup(request, sk):
    n_dim = request.param
    rng = np.random.default_rng(n_dim)
    docs = _unit(rng, NUM_DOCS, n_dim)
    dense = rlwe.build_candidate_cache(PARAMS, docs)
    q_cts = [rlwe.encrypt_query(sk, q, rng) for q in _unit(rng, 8, n_dim)]
    return n_dim, docs, dense, q_cts


def _device_pool(dense):
    """The dense cache's device rows, unflattened to (docs, chunks, P, N)."""
    return np.asarray(dense.polys).reshape(dense.host_pool().shape)


def _sharded(dense, **kw):
    kw.setdefault("shard_docs", SHARD_DOCS)
    return rlwe.shard_candidate_cache(dense,
                                      rlwe.CandidateCacheConfig(**kw))


def test_shard_geometry_and_pool_accounting(setup):
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense)
    assert sh.num_shards == -(-NUM_DOCS // SHARD_DOCS)
    assert sh.shard_docs == SHARD_DOCS
    assert (sh.n_dim, sh.num_docs) == (n_dim, NUM_DOCS)
    assert (sh.stride, sh.cands_per_ct, sh.num_chunks) == (
        dense.stride, dense.cands_per_ct, dense.num_chunks)
    # the shard pool is exactly the dense pool, re-viewed
    assert sh.pool_nbytes == dense.nbytes
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(s) for s in sh.shards]),
        dense.host_pool())
    assert sh.shard_of(0) == 0 and sh.shard_of(NUM_DOCS - 1) == 4
    # nothing resident before the first gather
    assert sh.resident_bytes == 0 and sh.resident_shards == ()


def test_build_sharded_matches_shard_of_dense(setup):
    n_dim, docs, dense, _ = setup
    built = rlwe.build_sharded_candidate_cache(
        PARAMS, docs, config=rlwe.CandidateCacheConfig(num_shards=4))
    rev = _sharded(dense, shard_docs=built.shard_docs)
    assert built.num_shards == rev.num_shards
    for a, b in zip(built.shards, rev.shards):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(built.twiddles),
                                  np.asarray(dense.twiddles))


@pytest.mark.parametrize("bsz", [1, 3, 8])
@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla", "pallas"])
def test_sharded_bit_identical_to_dense_and_cold(setup, bsz, use_pallas):
    n_dim, docs, dense, q_cts = setup
    rng = np.random.default_rng(bsz)
    ids = rng.integers(0, NUM_DOCS, size=(bsz, KPRIME))
    packed = rlwe.pack_candidates_batch(PARAMS, docs[ids])
    cold = rlwe.encrypted_scores_batch_stacked(
        PARAMS, q_cts[:bsz], packed, KPRIME, n_dim, use_pallas=use_pallas)
    cached = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:bsz], dense, ids, use_pallas=use_pallas)
    sh = _sharded(dense, max_resident_bytes=2 * dense.nbytes // 5)
    sharded = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:bsz], sh, ids, use_pallas=use_pallas)
    for a, b in ((cold, cached), (cold, sharded)):
        np.testing.assert_array_equal(np.asarray(a.c0), np.asarray(b.c0))
        np.testing.assert_array_equal(np.asarray(a.c1), np.asarray(b.c1))
        assert (a.n_dim, a.num_cands) == (b.n_dim, b.num_cands)


def test_fused_intt_kernel_bit_identical_to_staged(setup):
    """ops.fused_rotate_hadamard_intt (Pallas and XLA) == the staged
    fused accumulate + standalone inverse NTT, coefficient-exactly."""
    n_dim, docs, dense, q_cts = setup
    rng = np.random.default_rng(7)
    ids = rng.integers(0, NUM_DOCS, size=(2, KPRIME))
    cpt, chunks = dense.cands_per_ct, dense.num_chunks
    num_ct = -(-KPRIME // cpt)
    pad = num_ct * cpt - KPRIME
    import jax.numpy as jnp
    g = dense.host_pool()[ids]              # (2, KPRIME, chunks, P, N)
    if pad:
        g = np.concatenate(
            [g, np.zeros((2, pad) + g.shape[2:], np.int32)], axis=1)
    c0 = jnp.stack([q.c0 for q in q_cts[:2]])
    for i, ctx in enumerate(PARAMS.ctxs):
        f0 = ntt_ops.ntt_fwd(c0[:, :, i, :], ctx, use_pallas=False)
        polys_i = jnp.asarray(g[..., i, :]).reshape(
            2, num_ct, cpt * chunks, PARAMS.n_poly)
        tw = dense.twiddles[i]
        acc0, acc1 = ntt_ops.fused_rotate_hadamard(
            polys_i, tw, f0, f0, ctx, use_pallas=False)
        want0 = np.asarray(ntt_ops.ntt_inv(acc0, ctx, use_pallas=False))
        want1 = np.asarray(ntt_ops.ntt_inv(acc1, ctx, use_pallas=False))
        for up in (False, True):
            got0, got1 = ntt_ops.fused_rotate_hadamard_intt(
                polys_i, tw, f0, f0, ctx, use_pallas=up)
            np.testing.assert_array_equal(want0, np.asarray(got0))
            np.testing.assert_array_equal(want1, np.asarray(got1))


def test_lru_eviction_and_repin_deterministic(setup):
    """A fixed access trace must produce the same hit/miss/eviction sequence
    and the same resident set on two fresh caches — and identical bits to
    the dense cache at every step of the trace.  ``async_admission=False``
    selects the synchronous first-touch mode this trace was written for
    (the async policy admits on 2nd touch, off-thread)."""
    n_dim, docs, dense, q_cts = setup
    budget = 2 * dense.nbytes // 5          # room for exactly 2 of 5 shards
    # gathers process touched shards in sorted order (np.unique), so:
    trace = [np.array([[0, 1, 8, 9]]),       # miss 0, miss 1 -> (0, 1)
             np.array([[16, 17, 0, 1]]),     # hit 0 (-> MRU), miss 2,
                                             # evict 1 -> (0, 2)
             np.array([[8, 9, 8, 9]]),       # miss 1, evict 0 -> (2, 1)
             np.array([[32, 33, 39, 0]])]    # miss 0 evicts 2, miss 4
                                             # evicts 1 -> (0, 4)
    logs = []
    for _ in range(2):
        sh = _sharded(dense, max_resident_bytes=budget,
                      async_admission=False)
        log = []
        for ids in trace:
            got = rlwe.encrypted_scores_cached_batch(
                PARAMS, q_cts[:1], sh, ids, use_pallas=False)
            want = rlwe.encrypted_scores_cached_batch(
                PARAMS, q_cts[:1], dense, ids, use_pallas=False)
            np.testing.assert_array_equal(np.asarray(want.c0),
                                          np.asarray(got.c0))
            log.append((sh.hits, sh.misses, sh.evictions,
                        sh.resident_shards))
        logs.append(log)
        assert sh.resident_bytes <= budget
    assert logs[0] == logs[1], "eviction must be deterministic"
    # the semantics of the trace, not just reproducibility:
    hits, misses, evictions, resident = logs[0][-1]
    assert (hits, misses, evictions) == (1, 6, 4)
    assert resident == (0, 4)               # LRU -> MRU after the last step
    assert evictions == misses - len(resident)


def test_stream_only_budget_zero(setup):
    n_dim, docs, dense, q_cts = setup
    sh = _sharded(dense, max_resident_bytes=0)
    ids = np.arange(KPRIME)[None] % NUM_DOCS
    got = rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], sh, ids)
    want = rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], dense, ids)
    np.testing.assert_array_equal(np.asarray(want.c0), np.asarray(got.c0))
    assert sh.resident_shards == () and sh.evictions == 0
    assert sh.misses > 0 and sh.gathered_bytes > 0
    # a shard bigger than the whole budget is never pinned either
    tight = _sharded(dense, max_resident_bytes=dense.nbytes // 5 - 1)
    rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], tight, ids)
    assert tight.resident_shards == ()


def test_pin_on_access_false_keeps_resident_set_fixed(setup):
    n_dim, docs, dense, q_cts = setup
    sh = _sharded(dense, pin_on_access=False)
    sh.pin(2)
    assert sh.resident_shards == (2,)
    ids = np.array([[0, 8, 16, 17]])        # shards 0, 1 miss; 2 hits
    got = rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], sh, ids)
    want = rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], dense, ids)
    np.testing.assert_array_equal(np.asarray(want.c0), np.asarray(got.c0))
    assert sh.resident_shards == (2,) and sh.hits == 1 and sh.misses == 2


def test_gather_rows_match_pool(setup):
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, NUM_DOCS, size=(2, 5))
    g = np.asarray(sh.gather(ids))
    np.testing.assert_array_equal(g, dense.host_pool()[ids])


def test_sharded_scores_decrypt_like_cold(setup, sk):
    n_dim, docs, dense, q_cts = setup
    sh = _sharded(dense, max_resident_bytes=0)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, NUM_DOCS, size=(1, KPRIME))
    got = rlwe.decrypt_scores(
        sk, rlwe.encrypted_scores_cached(PARAMS, q_cts[0], sh, ids[0]))
    want = rlwe.decrypt_scores(
        sk, rlwe.encrypted_scores(
            PARAMS, q_cts[0], rlwe.pack_candidates(PARAMS, docs[ids[0]])))
    np.testing.assert_array_equal(got, want)


def test_sharded_cache_rejects_mismatched_params(setup):
    n_dim, docs, dense, q_cts = setup
    sh = _sharded(dense)
    other = rlwe.RlweParams(n_poly=1024, chunk=256)
    with pytest.raises(ValueError, match="rebuild the cache"):
        sh.check_compatible(other)
    with pytest.raises(ValueError, match="n_dim"):
        sh.check_compatible(PARAMS, n_dim=n_dim + 64)
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="rebuild the cache"):
        rlwe.encrypted_scores_cached_batch(other, q_cts[:1], sh, ids)


def test_index_memoizes_per_params_and_config(setup):
    from repro.retrieval.index import FlatIndex
    n_dim, docs, _, _ = setup
    index = FlatIndex.build(docs, normalize=False)
    cfg = rlwe.CandidateCacheConfig(shard_docs=SHARD_DOCS)
    a = index.candidate_cache(PARAMS, cfg)
    assert isinstance(a, rlwe.ShardedCandidateCache)
    # same (params value, config) -> same build; dense keyed separately
    assert index.candidate_cache(
        rlwe.RlweParams(n_poly=1024, chunk=512),
        rlwe.CandidateCacheConfig(shard_docs=SHARD_DOCS)) is a
    dense = index.candidate_cache(PARAMS)
    assert isinstance(dense, rlwe.CandidateCache) and dense is not a
    assert index.candidate_cache(
        PARAMS, rlwe.CandidateCacheConfig(shard_docs=4)) is not a
    # peek never builds
    assert index.peek_candidate_cache(PARAMS, cfg) is a
    assert index.peek_candidate_cache(
        PARAMS, rlwe.CandidateCacheConfig(shard_docs=5)) is None
    # one packed pool per params value: later configs re-view the donor's
    # pool instead of re-packing the corpus (dense included)
    b = index.candidate_cache(PARAMS, rlwe.CandidateCacheConfig(shard_docs=4))
    assert b.pool is a.pool
    assert dense.host_pool() is a.pool
    np.testing.assert_array_equal(_device_pool(dense), a.pool)


def test_admission_never_exceeds_budget_transiently(setup):
    """Eviction happens before the admission copy: with a budget of one
    shard, the resident set is exactly the last-touched shard and peak
    never exceeds the budget."""
    n_dim, docs, dense, q_cts = setup
    one_shard = dense.nbytes // 5
    sh = _sharded(dense, max_resident_bytes=one_shard,
                  async_admission=False)
    for ids in ([[0, 1]], [[8, 9]], [[0, 16]]):
        rlwe.encrypted_scores_cached_batch(
            PARAMS, q_cts[:1], sh, np.asarray(ids))
        assert sh.resident_bytes <= one_shard
    assert sh.peak_resident_bytes <= one_shard
    assert sh.resident_shards == (2,)       # last touched (sorted order)


def test_gather_rejects_out_of_range_ids(setup):
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense)
    with pytest.raises(IndexError, match="candidate ids"):
        sh.gather(np.array([[0, -1]]))
    with pytest.raises(IndexError, match="candidate ids"):
        sh.gather(np.array([[NUM_DOCS]]))


def test_dense_cache_shares_memoized_host_pool(setup):
    """shard_candidate_cache from a dense cache re-views the memoized host
    pool — one host array no matter how many configs consume it."""
    n_dim, docs, dense, _ = setup
    sh1 = _sharded(dense, shard_docs=8)
    sh2 = _sharded(dense, shard_docs=4)
    assert sh1.pool is dense.host_pool() and sh2.pool is dense.host_pool()


def test_config_rejects_nonpositive_sharding():
    with pytest.raises(ValueError, match="shard_docs must be positive"):
        rlwe.CandidateCacheConfig(shard_docs=0).resolve_shard_docs(10)
    with pytest.raises(ValueError, match="num_shards must be positive"):
        rlwe.CandidateCacheConfig(num_shards=0).resolve_shard_docs(10)


def test_densify_roundtrip(setup):
    n_dim, docs, dense, q_cts = setup
    sh = _sharded(dense)
    back = rlwe.densify_candidate_cache(sh)
    np.testing.assert_array_equal(_device_pool(back), dense.host_pool())
    resharded = rlwe.shard_candidate_cache(sh,
                                           rlwe.CandidateCacheConfig(
                                               shard_docs=4))
    assert resharded.pool is sh.pool        # no re-pack, no copy
    ids = np.arange(KPRIME)[None] % NUM_DOCS
    a = rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], back, ids)
    b = rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], resharded, ids)
    np.testing.assert_array_equal(np.asarray(a.c0), np.asarray(b.c0))


# ---------------------------------------------------------------------------
# async, frequency-aware admission policy
# ---------------------------------------------------------------------------

def test_async_admission_converges_to_sync_resident_set(setup):
    """With admit_threshold=1, the async admitter must converge (after a
    flush) to exactly the synchronous first-touch LRU state under a fixed
    trace — same resident set/order and same hit/miss counts at each step."""
    n_dim, docs, dense, _ = setup
    budget = 2 * dense.nbytes // 5
    trace = [np.array([[0, 1, 8, 9]]), np.array([[16, 17, 0, 1]]),
             np.array([[8, 9, 8, 9]]), np.array([[32, 33, 39, 0]])]
    sync = _sharded(dense, max_resident_bytes=budget, async_admission=False)
    asy = _sharded(dense, max_resident_bytes=budget, admit_threshold=1)
    for ids in trace:
        sync.gather(ids)
        asy.gather(ids)
        asy.flush()
        assert asy.resident_shards == sync.resident_shards
        assert (asy.hits, asy.misses) == (sync.hits, sync.misses)
    assert asy.evictions == sync.evictions
    assert asy.async_admissions == asy.admissions == sync.admissions


def test_gather_bit_identical_while_admission_in_flight(setup):
    """`gather` streams from the host pool while the admitter copy is in
    flight; the scores must be bit-identical to the dense cache before,
    during, and after the atomic swap-in."""
    n_dim, docs, dense, q_cts = setup
    sh = _sharded(dense, admit_threshold=1)
    started, release = threading.Event(), threading.Event()

    def hook(_s):                   # hold the copy mid-flight
        started.set()
        assert release.wait(30)
    sh._admit_hook = hook

    ids = np.array([[0, 1, 2, 3, 8, 9]])    # shards 0 and 1
    want = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:1], dense, ids, use_pallas=False)
    cold = rlwe.encrypted_scores_batch_stacked(
        PARAMS, q_cts[:1], rlwe.pack_candidates_batch(PARAMS, docs[ids]),
        ids.shape[1], n_dim, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(want.c0), np.asarray(cold.c0))
    got_cold = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:1], sh, ids, use_pallas=False)     # enqueues 0, 1
    assert started.wait(30)
    assert sh.stats()["pending_admissions"] > 0
    got_inflight = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:1], sh, ids, use_pallas=False)     # streams, no block
    release.set()
    sh.flush()
    assert sh.resident_shards == (0, 1)
    got_resident = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:1], sh, ids, use_pallas=False)     # device take
    for got in (got_cold, got_inflight, got_resident):
        np.testing.assert_array_equal(np.asarray(want.c0),
                                      np.asarray(got.c0))
        np.testing.assert_array_equal(np.asarray(want.c1),
                                      np.asarray(got.c1))
    assert sh.hits >= 2             # the post-swap gather hit both shards


def test_second_touch_never_admits_one_shot_sweep(setup):
    """The 2nd-touch policy must not admit anything under a uniform
    one-shot sweep (every shard touched exactly once)."""
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense)            # defaults: async, admit_threshold=2
    for lo in range(0, NUM_DOCS, SHARD_DOCS):
        sh.gather(np.array([[lo, lo + 1]]))     # one touch per shard
    sh.flush()
    st = sh.stats()
    assert st["resident_shards"] == ()
    assert st["admit_enqueued"] == st["admissions"] == 0
    assert st["policy_deferrals"] == sh.num_shards
    assert st["misses"] == sh.num_shards
    # ... while a second pass (repeat traffic) admits everything in range
    for lo in range(0, NUM_DOCS, SHARD_DOCS):
        sh.gather(np.array([[lo, lo + 1]]))
    sh.flush()
    assert len(sh.resident_shards) > 0
    assert sh.stats()["async_admissions"] > 0


def test_auto_window_sustained_uniform_never_admits(setup):
    """The auto admit_window (= num_shards for >= 8 shards) makes
    *sustained* uniform traffic decay every counter before its second
    touch: many full-corpus sweeps admit nothing, while skewed traffic on
    the same config admits after one repeat."""
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense, shard_docs=4)          # 10 shards, auto window 10
    assert sh.admit_window == 10
    uniform = np.arange(0, NUM_DOCS, 4)[None]   # every shard, every gather
    for _ in range(6):
        sh.gather(uniform)
    sh.flush()
    st = sh.stats()
    assert st["resident_shards"] == () and st["admit_enqueued"] == 0
    assert st["policy_deferrals"] == 6 * sh.num_shards
    # same config, skewed ids (2 of 10 shards): admitted on the 2nd gather
    sk = _sharded(dense, shard_docs=4)
    for _ in range(3):
        sk.gather(np.array([[0, 1, 4, 5]]))     # shards 0, 1 only
    sk.flush()
    assert set(sk.resident_shards) == {0, 1}


def test_touch_counter_decay_ages_out_stale_popularity(setup):
    """One touch, then a full decay window of other-shard traffic, then a
    second touch: the first touch must have aged out, so no admission."""
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense, admit_window=4)
    sh.gather(np.array([[0]]))              # shard 0: count 1
    for lo in (8, 16, 24):                  # 3 more touches -> window ends,
        sh.gather(np.array([[lo]]))         # counters halve and age out
    sh.gather(np.array([[0]]))              # shard 0 again: count back to 1
    sh.flush()
    assert sh.resident_shards == () and sh.admit_enqueued == 0
    # without decay the same trace admits shard 0
    sh2 = _sharded(dense, admit_window=1024)
    for lo in (0, 8, 16, 24, 0):
        sh2.gather(np.array([[lo]]))
    sh2.flush()
    assert 0 in sh2.resident_shards


def test_admit_queue_bounded_drops_are_counted(setup):
    """The admit queue is bounded: with the worker blocked, excess
    admission requests are dropped (and counted), never accumulated."""
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense, admit_threshold=1, max_pending_admissions=1)
    started, release = threading.Event(), threading.Event()

    def hook(_s):
        started.set()
        assert release.wait(30)
    sh._admit_hook = hook
    sh.gather(np.array([[0, 8, 16, 24, 32]]))   # 5 shards, queue cap 1
    assert started.wait(30)
    st = sh.stats()
    assert st["admit_dropped"] >= 2             # worker holds 1, queue 1
    release.set()
    sh.flush()
    assert len(sh.resident_shards) <= 2
    # dropped shards stay eligible: their counter kept them over threshold.
    # Each gather+flush round admits at least one more shard (the queue may
    # still drop some mid-gather — the worker races the touch loop), so a
    # few rounds converge to everything resident.
    for _ in range(4):
        sh.gather(np.array([[0, 8, 16, 24, 32]]))
        sh.flush()
    assert len(sh.resident_shards) == 5


def test_failed_admission_copy_is_counted(setup):
    """A background copy that raises (on a device: an HBM allocation
    failure) is counted with its error, leaves nothing resident, and the
    next touch retries it."""
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense, admit_threshold=1)
    fail = [True]

    def hook(_s):
        if fail[0]:
            raise MemoryError("RESOURCE_EXHAUSTED: shard copy")
    sh._admit_hook = hook
    sh.gather(np.array([[0]]))
    sh.flush()
    st = sh.stats()
    assert st["admit_failed"] == 1 and sh.resident_shards == ()
    assert "RESOURCE_EXHAUSTED" in st["last_admit_error"]
    fail[0] = False
    sh.gather(np.array([[0]]))
    sh.flush()
    assert sh.resident_shards == (0,) and sh.stats()["admit_failed"] == 1


def test_prefetch_counts_touch_once_and_overlaps(setup):
    """A prefetch records the touch; the request's own gather of the same
    ids must not double-count it (otherwise every request would hit the
    2nd-touch threshold immediately)."""
    n_dim, docs, dense, q_cts = setup
    sh = _sharded(dense)                        # threshold 2
    ids = np.array([[0, 1, 8]])                 # shards 0, 1
    assert sh.prefetch(ids) == 2
    rlwe.encrypted_scores_cached_batch(PARAMS, q_cts[:1], sh, ids,
                                       use_pallas=False)
    sh.flush()
    assert sh.resident_shards == ()             # single touch: no admission
    assert sh.stats()["prefetches"] == 2
    assert sh.stats()["policy_deferrals"] == 2
    # second request for the same region reaches the threshold at prefetch
    # time — the admission is enqueued before the gather even runs
    assert sh.prefetch(ids) == 2
    sh.flush()
    assert sh.resident_shards == (0, 1)
    assert sh.stats()["async_admissions"] == 2
    # stream-only caches still account prefetches but never admit
    sh0 = _sharded(dense, max_resident_bytes=0)
    assert sh0.prefetch(ids) == 2 and sh0.prefetch(ids) == 2
    sh0.flush()
    assert sh0.resident_shards == () and sh0.stats()["prefetches"] == 4


def test_prefetch_rejects_out_of_range_ids(setup):
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense)
    with pytest.raises(IndexError, match="candidate ids"):
        sh.prefetch(np.array([[0, NUM_DOCS]]))
    assert sh.prefetch(np.empty((1, 0), np.int64)) == 0


def test_async_cache_close_is_idempotent(setup):
    n_dim, docs, dense, _ = setup
    sh = _sharded(dense, admit_threshold=1)
    sh.gather(np.array([[0, 8]]))
    sh.close()
    sh.close()                                  # idempotent
    assert sh.stats()["pending_admissions"] == 0
    # the cache stays usable (and can admit again) after close
    sh.gather(np.array([[16]]))
    sh.flush()
    assert 16 // SHARD_DOCS in sh.resident_shards


def test_config_rejects_bad_admission_knobs():
    with pytest.raises(ValueError, match="admit_threshold"):
        rlwe.CandidateCacheConfig(admit_threshold=0)
    with pytest.raises(ValueError, match="admit_window"):
        rlwe.CandidateCacheConfig(admit_window=0)
    with pytest.raises(ValueError, match="max_pending_admissions"):
        rlwe.CandidateCacheConfig(max_pending_admissions=0)


def test_serve_engine_sharded_cache_end_to_end():
    """The engine on a sharded-cache config returns the same docs/ids as on
    the dense cache, and exposes LRU stats."""
    import jax
    from repro.retrieval.index import FlatIndex
    from repro.serve import EngineConfig, ServeEngine, SessionManager

    n_dim, n_docs, k = 128, 60, 3
    rng = np.random.default_rng(11)
    docs = _unit(rng, n_docs, n_dim)
    texts = [f"doc-{i}".encode() for i in range(n_docs)]

    def run(cache_config):
        index = FlatIndex.build(docs, documents=texts, normalize=False)
        engine = ServeEngine(
            index,
            config=EngineConfig(max_batch=3, use_candidate_cache=True,
                                cache_config=cache_config),
            sessions=SessionManager(rlwe_params=PARAMS,
                                    deterministic_seeds=True))
        for t in ("a", "b", "c"):
            engine.open_session(t, n=n_dim, N=n_docs, k=k, radius=0.05)
        for qi, t in enumerate(("a", "b", "c")):
            engine.submit(t, docs[qi], key=jax.random.PRNGKey(qi))
        return engine, engine.drain()

    cfg = rlwe.CandidateCacheConfig(shard_docs=16, max_resident_bytes=0)
    eng_dense, res_dense = run(None)
    eng_shard, res_shard = run(cfg)
    assert eng_dense.cache_stats() is None
    stats = eng_shard.cache_stats()
    assert stats is not None and stats["misses"] > 0
    # the admission/prefetch counters are part of the observability surface
    for key in ("admissions", "async_admissions", "prefetches",
                "admit_enqueued", "admit_dropped", "policy_deferrals",
                "pending_admissions"):
        assert key in stats
    # stream-only engine config: the prefetch hook still fires per batch
    # (the touches are counted) but nothing is ever admitted
    assert stats["prefetches"] > 0
    assert stats["admissions"] == 0 and stats["resident_shards"] == ()
    for a, b in zip(res_dense, res_shard):
        assert a.tenant == b.tenant
        np.testing.assert_array_equal(a.ids, b.ids)
        assert a.docs == b.docs
        assert a.transcript.total_bytes == b.transcript.total_bytes
