"""repro.kernels.bignum: RNS limb-array Montgomery arithmetic, differential
against CPython's arbitrary-precision ``pow``/``*`` at Paillier-relevant
modulus sizes (n^2 for 256- and 512-bit n)."""

import numpy as np
import pytest

import jax

from repro.crypto import paillier as pai
from repro.kernels.bignum import ops, ref

# n^2 moduli exactly as the Paillier backend sees them
KEY_BITS = (256, 512)


@pytest.fixture(scope="module", params=KEY_BITS, ids=lambda b: f"kb{b}")
def ctx(request):
    sk = pai.keygen(request.param, rng=np.random.default_rng(request.param))
    return ref.for_modulus(sk.pub.n_sq)


def _rand_ints(rng, modulus, count):
    return [int(rng.integers(0, 2**62)) * int(rng.integers(0, 2**62))
            % modulus for _ in range(count)]


# -- channel system ---------------------------------------------------------


def test_channel_primes_are_distinct_odd_primes():
    primes = ref._channel_primes(48)
    assert len(set(primes)) == 48
    for p in primes:
        assert p < ref.RADIX and p % 2 == 1
        assert all(p % d for d in range(2, int(p**0.5) + 1))


def test_num_channels_and_fits_boundaries():
    # kb-bit keys score mod n^2 (~2*kb bits): 256/512 fit the vectorized
    # budget, 1024 falls back to the object path
    for kb, should_fit in ((256, True), (512, True), (1024, False)):
        m = (1 << 2 * kb) - 1
        s = ref.num_channels(m)
        assert s == -(-(m.bit_length() + ref.HEADROOM_BITS) // ref.CH_BITS)
        assert ref.fits(m) == should_fit
        # the f64-exactness ceiling is a separate, harder bound
        assert ref.fits(m, budget=ref.HARD_CHANNELS)
    assert not ref.fits((1 << 2950) - 1, budget=ref.HARD_CHANNELS)


def test_incomplete_reduction_invariant():
    # correctness condition for the two approximate base extensions:
    # (s+1)^2 * 2^-(HEADROOM-1) <= 1 up to the channel budget
    for s in (2, 24, 46, ref.MAX_CHANNELS, ref.HARD_CHANNELS):
        assert (s + 1) ** 2 <= 2 ** (ref.HEADROOM_BITS - 1)


# -- reference implementation vs CPython bignums ----------------------------


def test_to_rns_from_rns_round_trip(ctx):
    rng = np.random.default_rng(1)
    vals = _rand_ints(rng, ctx.modulus, 17) + [0, 1, ctx.modulus - 1]
    back = ref.from_rns(ctx, ref.to_rns(ctx, vals))
    assert [v % ctx.modulus for v in back] == [v % ctx.modulus for v in vals]


def test_mont_mul_matches_python_pow(ctx):
    rng = np.random.default_rng(2)
    a = _rand_ints(rng, ctx.modulus, 9)
    b = _rand_ints(rng, ctx.modulus, 9)
    got = ref.from_rns(ctx, ref.mont_mul(ctx, ref.to_rns(ctx, [ref.to_mont(ctx, x) for x in a]),
                                         ref.to_rns(ctx, [ref.to_mont(ctx, y) for y in b])))
    for x, y, g in zip(a, b, got):
        assert ref.from_mont(ctx, g) % ctx.modulus == x * y % ctx.modulus


def test_mont_mul_chain_matches_python(ctx):
    # repeated squarings: the incomplete-reduction domain must not drift
    rng = np.random.default_rng(3)
    x = _rand_ints(rng, ctx.modulus, 1)[0]
    vec = ref.to_rns(ctx, [ref.to_mont(ctx, x)])
    want = x
    for _ in range(40):
        vec = ref.mont_mul(ctx, vec, vec)
        want = want * want % ctx.modulus
    got = ref.from_mont(ctx, ref.from_rns(ctx, vec)[0]) % ctx.modulus
    assert got == want


def test_mont_exp_matches_python_pow(ctx):
    rng = np.random.default_rng(4)
    base = _rand_ints(rng, ctx.modulus, 1)[0]
    for exp in (0, 1, 2, 3, 12345, ctx.modulus >> 7):
        got = ref.from_mont(ctx, ref.from_rns(ctx, ref.mont_exp(
            ctx, ref.to_rns(ctx, [ref.to_mont(ctx, base)]), exp))[0])
        assert got % ctx.modulus == pow(base, exp, ctx.modulus)


def test_modmul_helper(ctx):
    rng = np.random.default_rng(5)
    x, y = _rand_ints(rng, ctx.modulus, 2)
    assert ref.modmul(ctx, x, y) == x * y % ctx.modulus


# -- jitted ops vs the reference --------------------------------------------


def test_ops_mont_mul_matches_ref(ctx):
    rng = np.random.default_rng(6)
    a = _rand_ints(rng, ctx.modulus, 5)
    b = _rand_ints(rng, ctx.modulus, 5)
    am = ref.to_rns(ctx, [ref.to_mont(ctx, x) for x in a])
    bm = ref.to_rns(ctx, [ref.to_mont(ctx, y) for y in b])
    with jax.enable_x64(True):
        C = ops.make_consts(ctx.system, [ctx], batch_ndim=2)
        got = np.asarray(ops.mont_mul(am[None], bm[None], C))[0]
    want = ref.from_rns(ctx, ref.mont_mul(ctx, am, bm))
    assert ref.from_rns(ctx, got) == want


def test_ops_windowed_exp_matches_python_pow(ctx):
    rng = np.random.default_rng(7)
    bases = _rand_ints(rng, ctx.modulus, 3)
    exps = [int(rng.integers(1, 2**60)) for _ in bases]
    window = 4
    base = ref.to_rns(ctx, [ref.to_mont(ctx, x) for x in bases])[None]
    digits = ops.to_digits(exps, window)[None]
    with jax.enable_x64(True):
        C = ops.make_consts(ctx.system, [ctx], batch_ndim=2)
        table = ops.pow_table(base, C, window)
        got = np.asarray(ops.mont_exp_digits(table, digits, C, window))[0]
    for x, e, g in zip(bases, exps, ref.from_rns(ctx, got)):
        assert ref.from_mont(ctx, g) % ctx.modulus == pow(x, e, ctx.modulus)


@pytest.mark.parametrize("count", [1, 2, 5, 8])
def test_ops_product_reduce_matches_python(ctx, count):
    rng = np.random.default_rng(8 + count)
    xs = _rand_ints(rng, ctx.modulus, count)
    vec = ref.to_rns(ctx, [ref.to_mont(ctx, x) for x in xs])
    with jax.enable_x64(True):
        C = ops.make_consts(ctx.system, [ctx], batch_ndim=2)
        # product_reduce folds over axis -2; a [count, width] leaf block
        got = np.asarray(ops.product_reduce(vec[None], C))[0]
    want = 1
    for x in xs:
        want = want * x % ctx.modulus
    # the odd-aware tree performs count-1 mont_muls: one residual M factor
    g = ref.from_rns(ctx, got[None])[0]
    assert ref.from_mont(ctx, g) % ctx.modulus == want


def test_to_digits_round_trip():
    window = 5
    exps = [0, 1, 31, 32, 12345, 2**64 - 1]
    digits = ops.to_digits(exps, window)
    for e, row in zip(exps, digits):
        back = 0
        for d in row:
            back = (back << window) | int(d)
        assert back == e
