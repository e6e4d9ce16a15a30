"""Pallas TPU kernel: batched negacyclic NTT over an RNS prime.

Why a kernel: the RLWE encrypted-distance path (paper Module 2a, TPU-adapted)
is dominated by forward/inverse NTTs over batches of polynomials.  The whole
log2(N)-stage butterfly network runs on a VMEM-resident tile — one HBM read
and one HBM write per polynomial regardless of stage count, with the 10-bit
limb-split Barrett modular multiply (see `crypto/modring.py`) fused into every
butterfly.  All arithmetic is int32; every partial product is < 2^31, so the
kernel targets the TPU's native 32-bit integer lanes (no 64-bit emulation).

Layout: a polynomial of N = R * 128 coefficients is an (R, 128) tile —
coefficient j at row j // 128, lane j % 128 — so every block's last two
dims are the full (R, 128) plane and any batch size tiles legally (the
grid runs over the leading batch axis only).  A butterfly stage of
half-width t pairs coefficient j with j ^ t: for t >= 128 the partner is
t / 128 rows away, else t lanes away.  Each stage reads both partners with
two `pltpu.roll`s along that axis and picks per element by bit t of its
index, so no stage reshapes the lane axis.  Every output element is the
same `mod_add`/`mod_sub`/`mod_mul` of the same operands as in the
reshape-based reference (`kernels/ntt/ref.py`) — bit-identical by
construction.  Per-stage twiddles are precomputed as full (R, 128) planes
(`stage_twiddles`), so the kernel has no dynamic slicing.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.crypto import modring
from repro.crypto.modring import PrimeCtx

LANES = 128


def plane(n: int) -> tuple:
    """(rows, lanes) of the tile holding one N-coefficient polynomial."""
    assert n % LANES == 0, f"N={n} must be a multiple of {LANES}"
    return n // LANES, LANES


@functools.lru_cache(maxsize=None)
def stage_twiddles(ctx: PrimeCtx, inverse: bool) -> np.ndarray:
    """(log2 N, R, 128) int32: per butterfly stage, the twiddle of every
    coefficient's butterfly block — psi[m + j // 2t] forward (stage m,
    half-width t = N / 2m), ipsi[h + j // 2t] inverse (h = N / 2t)."""
    n = ctx.n
    table = ctx.ipsi_table if inverse else ctx.psi_table
    j = np.arange(n)
    halves = _half_widths(n, inverse)
    rows = [table[n // (2 * t) + j // (2 * t)] for t in halves]
    return np.stack(rows).astype(np.int32).reshape((len(rows),) + plane(n))


def _half_widths(n: int, inverse: bool) -> list:
    fwd = [n >> s for s in range(1, n.bit_length())]     # N/2, ..., 1
    return fwd[::-1] if inverse else fwd


def _partners(a, t: int):
    """(lo, hi, is_hi) for butterfly half-width t on (bt, R, 128) tiles:
    lo[j] = a[j & ~t], hi[j] = a[j | t], is_hi[j] = bit t of j."""
    axis, sh = (1, t // LANES) if t >= LANES else (2, t)
    size = a.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, a.shape[1:], axis - 1)
    is_hi = (idx & sh) != 0
    below = pltpu.roll(a, sh, axis)               # a[j - t]
    above = pltpu.roll(a, size - sh, axis)        # a[j + t]
    return jnp.where(is_hi, below, a), jnp.where(is_hi, a, above), is_hi


def fwd_butterflies(a, tw, *, q: int, mu: int):
    """Forward negacyclic (Cooley-Tukey, bit-reversed out) network on
    (bt, R, 128) int32 tiles; ``tw`` is `stage_twiddles(ctx, False)`."""
    n = a.shape[1] * a.shape[2]
    for s, t in enumerate(_half_widths(n, False)):
        lo, hi, is_hi = _partners(a, t)
        v = modring.mod_mul(hi, tw[s], q, mu)
        a = jnp.where(is_hi, modring.mod_sub(lo, v, q),
                      modring.mod_add(lo, v, q))
    return a


def inv_butterflies(a, tw, *, q: int, mu: int, n_inv: int):
    """Inverse negacyclic (Gentleman-Sande) network + final N^{-1} scaling
    on (bt, R, 128) int32 tiles; ``tw`` is `stage_twiddles(ctx, True)`.
    Shared by the standalone inverse-NTT kernel below and the fused re-rank
    kernel (`kernels/ntt/fused.py`), which absorbs the inverse NTT of its
    accumulators so both run the exact same integer ops."""
    n = a.shape[1] * a.shape[2]
    for s, t in enumerate(_half_widths(n, True)):
        lo, hi, is_hi = _partners(a, t)
        a = jnp.where(is_hi,
                      modring.mod_mul(modring.mod_sub(lo, hi, q), tw[s], q, mu),
                      modring.mod_add(lo, hi, q))
    return modring.mod_mul(a, jnp.int32(n_inv), q, mu)


def _fwd_kernel(x_ref, tw_ref, o_ref, *, q: int, mu: int):
    o_ref[...] = fwd_butterflies(x_ref[...], tw_ref[...], q=q, mu=mu)


def _inv_kernel(x_ref, tw_ref, o_ref, *, q: int, mu: int, n_inv: int):
    o_ref[...] = inv_butterflies(x_ref[...], tw_ref[...], q=q, mu=mu,
                                 n_inv=n_inv)


def _pointwise_kernel(a_ref, b_ref, o_ref, *, q: int, mu: int):
    o_ref[...] = modring.mod_mul(a_ref[...], b_ref[...], q, mu)


def _tile(batch: int, n: int) -> int:
    """Polynomials per grid step: the largest power of two dividing
    ``batch`` whose tile stays <= 256 KiB (the butterfly temporaries of a
    tile must fit the kernel's scoped VMEM several times over)."""
    bt = 1
    while (batch % (2 * bt) == 0 and 2 * bt * n * 4 <= (1 << 18)):
        bt *= 2
    return bt


def _rowwise_spec(batch: int, n: int, num_tiled: int, consts=()) -> dict:
    """pallas_call grid/specs for ``num_tiled`` (batch, R, 128) operands
    tiled along the batch axis; ``consts`` (e.g. the twiddle table) stay
    resident whole."""
    r, lanes = plane(n)
    bt = _tile(batch, n)
    tiled = pl.BlockSpec((bt, r, lanes), lambda i: (i, 0, 0))
    whole = [pl.BlockSpec(c.shape, lambda i, nd=c.ndim: (0,) * nd)
             for c in consts]
    return dict(
        grid=(batch // bt,),
        in_specs=[tiled] * num_tiled + whole,
        out_specs=tiled,
        out_shape=jax.ShapeDtypeStruct((batch, r, lanes), jnp.int32))


@functools.partial(jax.jit, static_argnames=("ctx", "inverse", "interpret"))
def ntt_pallas(x, ctx: PrimeCtx, *, inverse: bool = False, interpret: bool = True):
    """Batched (inverse) negacyclic NTT. x: (batch, N) int32 in [0, q)."""
    batch, n = x.shape
    assert n == ctx.n, (n, ctx.n)
    tw = jnp.asarray(stage_twiddles(ctx, inverse))
    if inverse:
        kern = functools.partial(_inv_kernel, q=ctx.q, mu=ctx.mu,
                                 n_inv=ctx.n_inv)
    else:
        kern = functools.partial(_fwd_kernel, q=ctx.q, mu=ctx.mu)
    out = pl.pallas_call(kern, interpret=interpret,
                         name="ntt_inv" if inverse else "ntt_fwd",
                         **_rowwise_spec(batch, n, 1, [tw]))(
        x.reshape((batch,) + plane(n)), tw)
    return out.reshape(batch, n)


@functools.partial(jax.jit, static_argnames=("ctx", "interpret"))
def pointwise_mul_pallas(a, b, ctx: PrimeCtx, *, interpret: bool = True):
    """Elementwise modular multiply of NTT-domain polynomials (same shape)."""
    assert a.shape == b.shape
    batch, n = a.shape
    out = pl.pallas_call(
        functools.partial(_pointwise_kernel, q=ctx.q, mu=ctx.mu),
        interpret=interpret, name="ntt_pointwise_mul",
        **_rowwise_spec(batch, n, 2))(
        a.reshape((batch,) + plane(n)), b.reshape((batch,) + plane(n)))
    return out.reshape(batch, n)


__all__ = ["ntt_pallas", "pointwise_mul_pallas", "fwd_butterflies",
           "inv_butterflies", "stage_twiddles", "plane", "LANES"]
