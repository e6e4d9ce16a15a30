"""NTT-domain candidate cache: cached+rotated scoring must be bit-identical
to fresh per-request packing (both strides, batch 1/3/8, fallback + fused
Pallas kernel), plus the monomial-rotation identity it rests on."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.crypto import modring, rlwe
from repro.crypto.modring import PrimeCtx
from repro.kernels.ntt import ops as ntt_ops

# n_dim=384 <= chunk -> stride=chunk (2 cands/ct); n_dim=768 > chunk ->
# stride=2*chunk (1 cand/ct, 2 chunks): both packing regimes.
PARAMS = rlwe.RlweParams(n_poly=1024, chunk=512)
NUM_DOCS = 40
KPRIME = 9          # not a multiple of cands_per_ct=2: pad path


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
    cache = rlwe.build_candidate_cache(PARAMS, docs)
    q_cts = [rlwe.encrypt_query(sk, q, rng) for q in _unit(rng, 8, n_dim)]
    return n_dim, docs, cache, q_cts, rng


def test_cache_hoists_packing_geometry(setup):
    n_dim, docs, cache, _, _ = setup
    assert cache.n_dim == n_dim and cache.num_docs == NUM_DOCS
    assert cache.stride == PARAMS.stride(n_dim)
    assert cache.cands_per_ct == PARAMS.cands_per_ct(n_dim)
    assert cache.num_chunks == PARAMS.num_chunks(n_dim)
    # memory contract: 4 * P * N bytes per chunk per doc
    assert cache.nbytes == (4 * PARAMS.num_primes * PARAMS.n_poly
                            * cache.num_chunks * NUM_DOCS)


@pytest.mark.parametrize("bsz", [1, 3, 8])
def test_cached_scoring_bit_identical_to_fresh_packing(setup, bsz):
    n_dim, docs, cache, q_cts, _ = setup
    rng = np.random.default_rng(bsz)
    ids = rng.integers(0, NUM_DOCS, size=(bsz, KPRIME))
    packed = rlwe.pack_candidates_batch(PARAMS, docs[ids])
    cold = rlwe.encrypted_scores_batch_stacked(
        PARAMS, q_cts[:bsz], packed, KPRIME, n_dim, use_pallas=False)
    cached = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:bsz], cache, ids, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(cold.c0), np.asarray(cached.c0))
    np.testing.assert_array_equal(np.asarray(cold.c1), np.asarray(cached.c1))
    assert (cold.n_dim, cold.num_cands) == (cached.n_dim, cached.num_cands)


def test_fused_pallas_kernel_bit_identical(setup):
    n_dim, docs, cache, q_cts, _ = setup
    rng = np.random.default_rng(99)
    ids = rng.integers(0, NUM_DOCS, size=(2, KPRIME))
    ref = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:2], cache, ids, use_pallas=False)
    kern = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:2], cache, ids, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(ref.c0), np.asarray(kern.c0))
    np.testing.assert_array_equal(np.asarray(ref.c1), np.asarray(kern.c1))


def test_cached_scores_decrypt_to_inner_products(setup, sk):
    n_dim, docs, cache, q_cts, rng = setup
    ids = rng.integers(0, NUM_DOCS, size=(1, KPRIME))
    res = rlwe.encrypted_scores_cached(PARAMS, q_cts[0], cache, ids[0])
    got = rlwe.decrypt_scores(sk, res)
    want = rlwe.decrypt_scores(
        sk, rlwe.encrypted_scores(
            PARAMS, q_cts[0], rlwe.pack_candidates(PARAMS, docs[ids[0]])))
    np.testing.assert_array_equal(got, want)


def test_single_query_wrapper_matches_batch_lane(setup):
    n_dim, docs, cache, q_cts, _ = setup
    ids = np.arange(KPRIME) % NUM_DOCS
    one = rlwe.encrypted_scores_cached(PARAMS, q_cts[0], cache, ids)
    bat = rlwe.encrypted_scores_cached_batch(
        PARAMS, q_cts[:1], cache, ids[None])
    assert isinstance(one, rlwe.ScoreCiphertexts)
    np.testing.assert_array_equal(np.asarray(one.c0), np.asarray(bat.c0[0]))


def test_cache_rejects_mismatched_params(setup):
    n_dim, docs, cache, q_cts, _ = setup
    other = rlwe.RlweParams(n_poly=1024, chunk=256)
    with pytest.raises(ValueError, match="rebuild the cache"):
        cache.check_compatible(other)
    ids = np.zeros((1, 4), np.int64)
    with pytest.raises(ValueError, match="rebuild the cache"):
        rlwe.encrypted_scores_cached_batch(other, q_cts[:1], cache, ids)
    # equal-valued params object is compatible (value key, not identity)
    cache.check_compatible(rlwe.RlweParams(n_poly=1024, chunk=512))
    with pytest.raises(ValueError, match="n_dim"):
        cache.check_compatible(PARAMS, n_dim=n_dim + 64)


def test_index_memoizes_cache_per_params_value(setup):
    from repro.retrieval.index import FlatIndex
    n_dim, docs, _, _, _ = setup
    index = FlatIndex.build(docs, normalize=False)
    a = index.candidate_cache(PARAMS)
    b = index.candidate_cache(rlwe.RlweParams(n_poly=1024, chunk=512))
    assert a is b                       # one build per params *value*
    c = index.candidate_cache(rlwe.RlweParams(n_poly=1024, chunk=256))
    assert c is not a
    assert c.num_chunks == -(-n_dim // 256)


def test_monomial_rotation_identity_hypothesis():
    """NTT(X^o * p) == NTT(X^o) . NTT(p) coefficient-exactly — the identity
    the candidate cache rests on — against the independent schoolbook
    negacyclic oracle."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    n = 256
    ctx = PrimeCtx.build(modring.find_ntt_primes(2 * n, 1)[0], n)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1),
           st.integers(min_value=0, max_value=n - 1))
    def prop(seed, offset):
        rng = np.random.default_rng(seed)
        p = rng.integers(0, ctx.q, size=(n,), dtype=np.int64).astype(np.int32)
        mono = np.zeros(n, np.int32)
        mono[offset] = 1
        rotated = modring.negacyclic_mul_np(mono, p, ctx.q).astype(np.int32)
        lhs = np.asarray(ntt_ops.ntt_fwd(rotated, ctx, use_pallas=False))
        tw = ntt_ops.ntt_fwd(mono, ctx, use_pallas=False)
        fp = ntt_ops.ntt_fwd(p, ctx, use_pallas=False)
        rhs = np.asarray(modring.mod_mul(jnp.asarray(tw), jnp.asarray(fp),
                                         ctx.q, ctx.mu))
        np.testing.assert_array_equal(lhs, rhs)

    prop()


# The served geometry in miniature: one chunk per document and 4 candidates
# per result ciphertext, as at N=4096 and 768 dims, and k' = 161, which
# leaves 3 empty slots in the last of 41 result ciphertexts.
SERVED_PARAMS = rlwe.RlweParams(n_poly=1024, chunk=256)
SERVED_DIM, SERVED_KPRIME, SERVED_DOCS = 192, 161, 300


@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(161)
    docs = _unit(rng, SERVED_DOCS, SERVED_DIM)
    cache = rlwe.build_candidate_cache(SERVED_PARAMS, docs)
    assert (cache.cands_per_ct, cache.num_chunks) == (4, 1)
    sk = rlwe.keygen(SERVED_PARAMS, rng)
    q_cts = [rlwe.encrypt_query(sk, q, rng)
             for q in _unit(rng, 8, SERVED_DIM)]
    return docs, cache, q_cts


@pytest.mark.parametrize("bsz", [1, 3, 8])
def test_pool_rows_score_like_fresh_packing(served, bsz):
    """The dense pool stored as one row per document scores bit-identically
    to fresh packing, also through a sharded re-view of that pool: the same
    ciphertexts, so the same decrypted scores and wire bytes."""
    docs, cache, q_cts = served
    rng = np.random.default_rng(bsz)
    ids = rng.integers(0, SERVED_DOCS, size=(bsz, SERVED_KPRIME))
    packed = rlwe.pack_candidates_batch(SERVED_PARAMS, docs[ids])
    fresh = rlwe.encrypted_scores_batch(
        SERVED_PARAMS, q_cts[:bsz], packed, SERVED_KPRIME, SERVED_DIM,
        use_pallas=False)
    cached = rlwe.encrypted_scores_cached_batch(
        SERVED_PARAMS, q_cts[:bsz], cache, ids, use_pallas=False)
    sharded = rlwe.encrypted_scores_cached_batch(
        SERVED_PARAMS, q_cts[:bsz],
        rlwe.shard_candidate_cache(
            cache, rlwe.CandidateCacheConfig(shard_docs=64)),
        ids, use_pallas=False)
    assert cached.c0.shape[1] == 41
    for got in (cached, sharded):
        for lane, want in zip(got.lanes(), fresh):
            np.testing.assert_array_equal(np.asarray(lane.c0),
                                          np.asarray(want.c0))
            np.testing.assert_array_equal(np.asarray(lane.c1),
                                          np.asarray(want.c1))


def test_host_pool_is_the_packed_pool(served):
    """Built or densified, the dense cache holds the packed pool as rows on
    the device, and `host_pool` gives it back as (docs, chunks, P, N)."""
    docs, cache, _ = served
    pool = rlwe._pack_corpus_ntt(SERVED_PARAMS, docs)
    dense = rlwe.densify_candidate_cache(rlwe.build_sharded_candidate_cache(
        SERVED_PARAMS, docs, config=rlwe.CandidateCacheConfig(num_shards=3)))
    for c in (cache, dense):
        assert c.polys.shape == (SERVED_DOCS, pool[0].size)
        assert c.nbytes == pool.nbytes
        np.testing.assert_array_equal(c.host_pool(), pool)
        np.testing.assert_array_equal(np.asarray(c.polys),
                                      pool.reshape(SERVED_DOCS, -1))
