"""Fused score+select kernel vs pure-jnp oracle: shape/dtype sweeps."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.scoretopk import ops, ref
from repro.kernels.scoretopk import scoretopk as kern


def _data(rng, b, n_rows, n, dtype=np.float32):
    q = rng.normal(size=(b, n)).astype(dtype)
    e = rng.normal(size=(n_rows, n)).astype(dtype)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    e /= np.linalg.norm(e, axis=-1, keepdims=True)
    return jnp.asarray(q), jnp.asarray(e)


@pytest.mark.parametrize("b,n_rows,n,kk,tile", [
    (1, 512, 128, 8, 256),
    (4, 1000, 384, 16, 256),     # non-multiple rows -> padding path
    (2, 4096, 768, 32, 2048),
    (8, 300, 64, 300, 512),      # kk > rows in tile tail
])
def test_kernel_matches_tile_oracle(b, n_rows, n, kk, tile):
    rng = np.random.default_rng(0)
    q, e = _data(rng, b, n_rows, n)
    kk_eff = min(kk, tile, n_rows)
    got_v, got_i = kern.score_topk_pallas(q, e, kk=kk_eff, tile=tile)
    want_v, want_i = ref.tile_topk_ref(q, e, kk_eff, tile)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-6, atol=1e-6)
    finite = np.isfinite(np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i)[finite],
                                  np.asarray(want_i)[finite])


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_kernel_dtypes(dtype):
    rng = np.random.default_rng(1)
    q, e = _data(rng, 2, 512, 128, dtype)
    got_v, got_i = kern.score_topk_pallas(q, e, kk=8, tile=256)
    want_v, want_i = ref.tile_topk_ref(q, e, 8, 256)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-5, atol=1e-5)


def test_topk_end_to_end_exact():
    rng = np.random.default_rng(2)
    q, e = _data(rng, 3, 5000, 256)
    out = ops.topk_scores(q, e, k=25, tile=1024, use_pallas=True)
    want_v, want_i = ref.topk_ref(q, e, 25)
    assert bool(out.exact)
    np.testing.assert_allclose(np.asarray(out.values), np.asarray(want_v),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out.indices), np.asarray(want_i))


def test_topk_certificate_path():
    """per_tile_k < k: certificate true on benign data, result still exact."""
    rng = np.random.default_rng(3)
    q, e = _data(rng, 2, 8192, 128)
    out = ops.topk_scores(q, e, k=64, tile=1024, per_tile_k=32, use_pallas=True)
    want_v, want_i = ref.topk_ref(q, e, 64)
    if bool(out.exact):
        np.testing.assert_array_equal(np.asarray(out.indices),
                                      np.asarray(want_i))


def test_certificate_detects_adversarial_tile():
    """All winners in one tile with kk < k: certificate must be False."""
    n, k = 64, 16
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, n)).astype(np.float32))
    base = rng.normal(size=(2048, n)).astype(np.float32) * 0.01
    base[:32] = np.asarray(q)[0] * 10.0  # tile 0 dominates with 32 >= kk=8 rows
    out = ops.topk_scores(q, jnp.asarray(base), k=k, tile=256, per_tile_k=8,
                          use_pallas=True)
    assert not bool(out.exact)
    # fallback recovers exactness
    fb = ops.exact_fallback(q, jnp.asarray(base), k)
    want_v, _ = ref.topk_ref(q, jnp.asarray(base), k)
    np.testing.assert_allclose(np.asarray(fb.values), np.asarray(want_v),
                               rtol=1e-6)


def test_small_corpus_single_tile():
    rng = np.random.default_rng(5)
    q, e = _data(rng, 2, 100, 32)
    out = ops.topk_scores(q, e, k=10, tile=2048, use_pallas=True)
    want_v, want_i = ref.topk_ref(q, e, 10)
    np.testing.assert_array_equal(np.asarray(out.indices), np.asarray(want_i))


def test_reference_scores_are_shape_independent():
    """Every reference score is a pure function of its (query, row) pair:
    a solo query, a sub-batch or a corpus slice reproduces the full
    scan's bits, so exact ties stay ties across the router's slices."""
    rng = np.random.default_rng(7)
    q, e = _data(rng, 11, 1500, 64)
    full = np.asarray(ref.score_ref(q, e))
    for (b0, b1), (r0, r1) in [((3, 4), (0, 1500)), ((0, 8), (750, 1500)),
                               ((2, 11), (375, 1125)), ((5, 6), (1499, 1500))]:
        np.testing.assert_array_equal(
            np.asarray(ref.score_ref(q[b0:b1], e[r0:r1])),
            full[b0:b1, r0:r1])


def test_reference_scan_scratch_is_linear():
    """The XLA reference scan (the `use_pallas=False` path, the default on
    the CPU) is one jitted program with O(B * N) scratch: no (B, N, n)
    product is materialized (245 MB here, 24.6 GB at 10^6 x 768)."""
    b, rows, n = 8, 10_000, 768
    lowered = ref.tile_topk_ref.lower(
        jax.ShapeDtypeStruct((b, n), jnp.float32),
        jax.ShapeDtypeStruct((rows, n), jnp.float32), 154, 1024)
    assert lowered.compile().memory_analysis().temp_size_in_bytes \
        < 64 * b * rows


def test_k_exceeds_corpus():
    rng = np.random.default_rng(6)
    q, e = _data(rng, 1, 17, 16)
    out = ops.topk_scores(q, e, k=40, use_pallas=True)
    assert out.indices.shape == (1, 17)
