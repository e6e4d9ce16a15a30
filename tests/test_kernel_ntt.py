"""Pallas NTT kernel vs pure-jnp ref vs independent numpy-int64 oracle."""

import numpy as np
import pytest

from repro.crypto import modring
from repro.crypto.modring import PrimeCtx
from repro.kernels.ntt import ops, ref


def _ctx(n=1024, which=0):
    primes = modring.find_ntt_primes(2 * n, which + 1)
    return PrimeCtx.build(primes[which], n)


# ---------------------------------------------------------------------------
# modular primitive correctness (int32-safe path vs int64)
# ---------------------------------------------------------------------------

def test_mod_mul_matches_int64():
    rng = np.random.default_rng(0)
    ctx = _ctx(256)
    a = rng.integers(0, ctx.q, size=(4096,)).astype(np.int32)
    b = rng.integers(0, ctx.q, size=(4096,)).astype(np.int32)
    got = np.asarray(modring.mod_mul(a, b, ctx.q, ctx.mu))
    want = modring.mod_mul_np(a, b, ctx.q).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_mod_mul_edge_values():
    ctx = _ctx(256)
    edge = np.array([0, 1, 2, ctx.q - 2, ctx.q - 1], dtype=np.int32)
    a, b = np.meshgrid(edge, edge)
    got = np.asarray(modring.mod_mul(a.ravel(), b.ravel(), ctx.q, ctx.mu))
    want = modring.mod_mul_np(a.ravel(), b.ravel(), ctx.q).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_barrett_full_range():
    ctx = _ctx(256)
    rng = np.random.default_rng(1)
    x = rng.integers(0, 2**31 - 1, size=(8192,)).astype(np.int32)
    got = np.asarray(modring.barrett_reduce(x, ctx.q, ctx.mu))
    np.testing.assert_array_equal(got, (x.astype(np.int64) % ctx.q).astype(np.int32))


# ---------------------------------------------------------------------------
# reference NTT correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [256, 1024])
def test_ref_roundtrip(n):
    ctx = _ctx(n)
    rng = np.random.default_rng(2)
    x = ref.random_poly(rng, (8, n), ctx.q)
    back = np.asarray(ops.ntt_inv(ops.ntt_fwd(x, ctx, use_pallas=False), ctx,
                                  use_pallas=False))
    np.testing.assert_array_equal(back, x)


@pytest.mark.parametrize("n", [256, 1024])
def test_ref_negacyclic_matches_schoolbook(n):
    ctx = _ctx(n)
    rng = np.random.default_rng(3)
    a = ref.random_poly(rng, (3, n), ctx.q)
    b = ref.random_poly(rng, (3, n), ctx.q)
    got = np.asarray(ops.negacyclic_mul(a, b, ctx, use_pallas=False))
    want = modring.negacyclic_mul_np(a, b, ctx.q).astype(np.int32)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode) vs reference — shape/prime sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [256, 1024, 4096])
@pytest.mark.parametrize("batch", [1, 8, 96])
def test_kernel_fwd_matches_ref(n, batch):
    ctx = _ctx(n)
    rng = np.random.default_rng(4)
    x = ref.random_poly(rng, (batch, n), ctx.q)
    got = np.asarray(ops.ntt_fwd(x, ctx, use_pallas=True))
    want = np.asarray(ops.ntt_fwd(x, ctx, use_pallas=False))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [256, 1024])
@pytest.mark.parametrize("which_prime", [0, 1, 2])
def test_kernel_roundtrip_all_primes(n, which_prime):
    ctx = _ctx(n, which=which_prime)
    rng = np.random.default_rng(5)
    x = ref.random_poly(rng, (16, n), ctx.q)
    y = ops.ntt_fwd(x, ctx, use_pallas=True)
    back = np.asarray(ops.ntt_inv(y, ctx, use_pallas=True))
    np.testing.assert_array_equal(back, x)


def test_kernel_negacyclic_matches_schoolbook():
    ctx = _ctx(1024)
    rng = np.random.default_rng(6)
    a = ref.random_poly(rng, (4, 1024), ctx.q)
    b = ref.random_poly(rng, (4, 1024), ctx.q)
    got = np.asarray(ops.negacyclic_mul(a, b, ctx, use_pallas=True))
    want = modring.negacyclic_mul_np(a, b, ctx.q).astype(np.int32)
    np.testing.assert_array_equal(got, want)


def test_kernel_leading_dims():
    ctx = _ctx(256)
    rng = np.random.default_rng(7)
    x = ref.random_poly(rng, (3, 5, 256), ctx.q)
    got = np.asarray(ops.ntt_fwd(x, ctx))
    want = np.asarray(ops.ntt_fwd(x.reshape(15, 256), ctx)).reshape(3, 5, 256)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# platform rule: compiled on a TPU, interpreted on the CPU, refused elsewhere
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_pallas_platform_rule(monkeypatch, platform, interpret):
    import repro.kernels as kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: platform)
    assert kernels.resolve_use_pallas(None) is (platform == "tpu")
    assert kernels.resolve_use_pallas(False) is False
    if interpret is None:
        with pytest.raises(RuntimeError, match="use_pallas=False"):
            kernels.interpret_mode()
    else:
        assert kernels.interpret_mode() is interpret
