"""Fused Pallas kernel for the cached encrypted re-rank hot path.

One ``pallas_call`` per RNS prime computes, for every (batch lane, result
ciphertext) grid cell, both NTT-domain accumulators of the cloud's ct (x) p:

    acc_z = sum_{s < cpt} sum_{c < chunks}  tw[s] . polys[s, c] . f_z[c]
                                                              (z in {0, 1})

where ``polys`` are the candidate-cache plaintexts (slot-0 packing, already
in the NTT domain), ``tw[s]`` is the NTT-domain diagonal of the monomial
X^{s*stride} (realizing the candidate's slot offset as a pointwise twiddle
rotate instead of a host repack + forward NTT), and ``f_z`` are the forward
NTTs of the query ciphertext components.  The old composition issued one
dispatch per (rotate, Hadamard, mod-add) stage; here rotate -> Hadamard(c0,
c1) -> slot/chunk accumulation run on a single VMEM-resident tile — one HBM
read of the gathered cache rows and one HBM write of the two accumulators.

Everything is int32: products are Barrett-reduced to [0, q), and the final
slot/chunk sum accumulates raw (cpt*chunks terms * q < 2^31, asserted) and
is reduced once — bit-identical to a chain of mod_add.

Two variants share the rotate/Hadamard/accumulate body:

  * `fused_rerank_pallas`       — NTT-domain accumulators out (the inverse
                                  NTT stays in the separate `ntt_pallas`
                                  dispatch; kept for staged comparisons).
  * `fused_rerank_intt_pallas`  — additionally absorbs the per-prime inverse
                                  NTT: the (acc0, acc1) pair of a grid cell
                                  is a (2, N) tile that runs the exact
                                  `inv_butterflies` network of the standalone
                                  kernel before leaving VMEM, so the result
                                  ciphertext components come out in the
                                  coefficient domain with no extra HBM
                                  round-trip.  This is the ROADMAP-named
                                  batch-8 bottleneck fix: cached scoring is
                                  Hadamard/iNTT-bound once packing is hoisted
                                  into the candidate cache.  The per-prime
                                  results are stacked into the RNS (CRT)
                                  ciphertext layout inside the same jit; the
                                  bignum CRT *lift* itself stays host-side at
                                  decryption — big_q ~ 2^60 cannot live in
                                  int32 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.crypto import modring
from repro.crypto.modring import PrimeCtx
from repro.kernels.ntt import ntt as _ntt


def _accumulate(polys_ref, tw_ref, f0_ref, f1_ref, *, q: int, mu: int,
                cpt: int, chunks: int):
    """Shared kernel body: twiddle rotate -> Hadamard(c0, c1) -> raw-sum ->
    one Barrett reduction.  Returns a (2, R, 128) tile [acc0; acc1] in
    [0, q) (polynomials in the `ntt.plane` layout)."""
    plane = polys_ref.shape[-2:]
    g = polys_ref[...].reshape((cpt, chunks) + plane)
    tw = tw_ref[...]                                    # (cpt, R, 128)
    f0 = f0_ref[...].reshape((1, chunks) + plane)
    f1 = f1_ref[...].reshape((1, chunks) + plane)
    rot = modring.mod_mul(g, tw[:, None], q, mu)        # slot twiddle rotate
    p0 = modring.mod_mul(rot, f0, q, mu).reshape((cpt * chunks,) + plane)
    p1 = modring.mod_mul(rot, f1, q, mu).reshape((cpt * chunks,) + plane)
    return jnp.stack([
        modring.barrett_reduce(jnp.sum(p0, axis=0), q, mu),
        modring.barrett_reduce(jnp.sum(p1, axis=0), q, mu)])


def _fused_kernel(polys_ref, tw_ref, f0_ref, f1_ref, o0_ref, o1_ref, *,
                  q: int, mu: int, cpt: int, chunks: int):
    acc = _accumulate(polys_ref, tw_ref, f0_ref, f1_ref, q=q, mu=mu,
                      cpt=cpt, chunks=chunks)
    o0_ref[...] = acc[0:1][None]
    o1_ref[...] = acc[1:2][None]


def _fused_intt_kernel(polys_ref, tw_ref, f0_ref, f1_ref, itw_ref,
                       o0_ref, o1_ref, *, q: int, mu: int, cpt: int,
                       chunks: int, n_inv: int):
    acc = _accumulate(polys_ref, tw_ref, f0_ref, f1_ref, q=q, mu=mu,
                      cpt=cpt, chunks=chunks)
    # absorb the inverse NTT: the (2, R, 128) accumulator tile runs the
    # exact butterfly network of the standalone kernel while still
    # VMEM-resident
    out = _ntt.inv_butterflies(acc, itw_ref[...], q=q, mu=mu, n_inv=n_inv)
    o0_ref[...] = out[0:1][None]
    o1_ref[...] = out[1:2][None]


def _rerank_call(kern, name: str, polys, tw, f0, f1, consts,
                 ctx: PrimeCtx, interpret: bool):
    """One grid cell per (batch lane, result ciphertext); every operand in
    the (R, 128) plane layout, so each block's last two dims are a whole
    plane — legal TPU tiling for any num_ct, rows and chunks."""
    bsz, num_ct, rows, n = polys.shape
    cpt, chunks = tw.shape[0], f0.shape[1]
    assert rows == cpt * chunks, (rows, cpt, chunks)
    assert n == ctx.n and f0.shape == f1.shape == (bsz, chunks, n)
    assert rows * (ctx.q - 1) < 2**31, "int32 accumulator would wrap"
    pl_shape = _ntt.plane(n)
    out = jax.ShapeDtypeStruct((bsz, num_ct) + pl_shape, jnp.int32)
    out_spec = pl.BlockSpec((1, 1) + pl_shape, lambda b, t: (b, t, 0, 0))
    query = pl.BlockSpec((1, chunks) + pl_shape, lambda b, t: (b, 0, 0, 0))
    acc0, acc1 = pl.pallas_call(
        kern,
        grid=(bsz, num_ct),
        in_specs=[
            pl.BlockSpec((1, 1, rows) + pl_shape,
                         lambda b, t: (b, t, 0, 0, 0)),
            pl.BlockSpec((cpt,) + pl_shape, lambda b, t: (0, 0, 0)),
            query, query,
        ] + [pl.BlockSpec(c.shape, lambda b, t, nd=c.ndim: (0,) * nd)
             for c in consts],
        out_specs=[out_spec, out_spec],
        out_shape=[out, out],
        interpret=interpret,
        name=name,
    )(polys.reshape((bsz, num_ct, rows) + pl_shape),
      tw.reshape((cpt,) + pl_shape),
      f0.reshape((bsz, chunks) + pl_shape),
      f1.reshape((bsz, chunks) + pl_shape), *consts)
    return acc0.reshape(bsz, num_ct, n), acc1.reshape(bsz, num_ct, n)


@functools.partial(jax.jit, static_argnames=("ctx", "interpret"))
def fused_rerank_pallas(polys, tw, f0, f1, ctx: PrimeCtx, *,
                        interpret: bool = True):
    """Rotate -> Hadamard(c0, c1) -> slot/chunk mod-sum for one prime.

    polys: (B, num_ct, cpt*chunks, N) gathered cache rows, slot-major;
    tw: (cpt, N) monomial twiddles; f0/f1: (B, chunks, N) query NTTs.
    Returns (acc0, acc1), each (B, num_ct, N) int32 in [0, q).
    """
    kern = functools.partial(_fused_kernel, q=ctx.q, mu=ctx.mu,
                             cpt=tw.shape[0], chunks=f0.shape[1])
    return _rerank_call(kern, "rerank_fused", polys, tw, f0, f1, (), ctx,
                        interpret)


@functools.partial(jax.jit, static_argnames=("ctx", "interpret"))
def fused_rerank_intt_pallas(polys, tw, f0, f1, ctx: PrimeCtx, *,
                             interpret: bool = True):
    """Rotate -> Hadamard(c0, c1) -> slot/chunk mod-sum -> inverse NTT for
    one prime, in a single kernel.

    Same contract as `fused_rerank_pallas` but the returned (acc0, acc1)
    are in the *coefficient* domain: each grid cell's accumulator pair is
    inverse-NTT'd as a (2, R, 128) tile before it leaves VMEM (the exact
    `inv_butterflies` network of `ntt_pallas`, so outputs are bit-identical
    to fused_rerank_pallas followed by the standalone inverse NTT).
    """
    kern = functools.partial(_fused_intt_kernel, q=ctx.q, mu=ctx.mu,
                             cpt=tw.shape[0], chunks=f0.shape[1],
                             n_inv=ctx.n_inv)
    itw = jnp.asarray(_ntt.stage_twiddles(ctx, True))
    return _rerank_call(kern, "rerank_fused_intt", polys, tw, f0, f1,
                        (itw,), ctx, interpret)


__all__ = ["fused_rerank_pallas", "fused_rerank_intt_pallas"]
