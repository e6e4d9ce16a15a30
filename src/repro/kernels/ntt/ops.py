"""Public jit'd API over the NTT kernel with an XLA fallback.

``use_pallas`` selects the Pallas kernel (interpret-mode on CPU, compiled on
TPU, refused elsewhere — see `repro.kernels`; ``None`` picks it on a TPU
only, and tests pass ``True`` to run the kernel in interpret mode); the
fallback is the pure-jnp reference, which XLA fuses reasonably but
round-trips HBM between stages on real hardware.
"""

from __future__ import annotations

import functools

import jax

from repro.crypto import modring
from repro.crypto.modring import PrimeCtx
from repro.kernels import interpret_mode as _interpret
from repro.kernels import resolve_use_pallas as _resolve
from repro.kernels.ntt import fused as _fused
from repro.kernels.ntt import ntt as _kern
from repro.kernels.ntt import ref as _ref


# PrimeCtx instances are interned per (q, n) and hash by identity, so they
# are valid static args: jitting here collapses the ~log2(N) stages of eager
# jnp dispatch in the reference path into one compiled call per shape —
# the serving hot loop on CPU is NTT-bound.
@functools.partial(jax.jit, static_argnames=("ctx",))
def _ntt_fwd_ref(x, ctx: PrimeCtx):
    return _ref.ntt_fwd_ref(x, ctx)


@functools.partial(jax.jit, static_argnames=("ctx",))
def _ntt_inv_ref(x, ctx: PrimeCtx):
    return _ref.ntt_inv_ref(x, ctx)


@functools.partial(jax.jit, static_argnames=("ctx",))
def _pointwise_mul_ref(a, b, ctx: PrimeCtx):
    return modring.mod_mul(a, b, ctx.q, ctx.mu)


@functools.partial(jax.jit, static_argnames=("ctx",))
def _fused_rotate_hadamard_ref(polys, tw, f0, f1, ctx: PrimeCtx):
    bsz, num_ct, rows, n = polys.shape
    cpt, chunks = tw.shape[0], f0.shape[1]
    g = polys.reshape(bsz, num_ct, cpt, chunks, n)
    rot = modring.mod_mul(g, tw[None, None, :, None, :], ctx.q, ctx.mu)
    p0 = modring.mod_mul(rot, f0[:, None, None], ctx.q, ctx.mu)
    p1 = modring.mod_mul(rot, f1[:, None, None], ctx.q, ctx.mu)
    return (modring.mod_sum(p0.reshape(bsz, num_ct, rows, n),
                            ctx.q, ctx.mu, axis=2),
            modring.mod_sum(p1.reshape(bsz, num_ct, rows, n),
                            ctx.q, ctx.mu, axis=2))


@functools.partial(jax.jit, static_argnames=("ctx",))
def _fused_rotate_hadamard_intt_ref(polys, tw, f0, f1, ctx: PrimeCtx):
    acc0, acc1 = _fused_rotate_hadamard_ref(polys, tw, f0, f1, ctx)
    return _ref.ntt_inv_ref(acc0, ctx), _ref.ntt_inv_ref(acc1, ctx)


def ntt_fwd(x, ctx: PrimeCtx, *, use_pallas=None):
    """Forward negacyclic NTT, (..., N) int32 in [0, q) -> bit-rev NTT domain."""
    use_pallas = _resolve(use_pallas)
    if not use_pallas:
        return _ntt_fwd_ref(x, ctx)
    lead = x.shape[:-1]
    flat = x.reshape((-1, ctx.n))
    out = _kern.ntt_pallas(flat, ctx, inverse=False, interpret=_interpret())
    return out.reshape(lead + (ctx.n,))


def ntt_inv(x, ctx: PrimeCtx, *, use_pallas=None):
    """Inverse negacyclic NTT, bit-rev NTT domain -> coefficient domain."""
    use_pallas = _resolve(use_pallas)
    if not use_pallas:
        return _ntt_inv_ref(x, ctx)
    lead = x.shape[:-1]
    flat = x.reshape((-1, ctx.n))
    out = _kern.ntt_pallas(flat, ctx, inverse=True, interpret=_interpret())
    return out.reshape(lead + (ctx.n,))


def pointwise_mul(a, b, ctx: PrimeCtx, *, use_pallas=None):
    """Hadamard modular product in the NTT domain."""
    use_pallas = _resolve(use_pallas)
    if not use_pallas:
        return _pointwise_mul_ref(a, b, ctx)
    lead = a.shape[:-1]
    fa = a.reshape((-1, ctx.n))
    fb = b.reshape((-1, ctx.n))
    out = _kern.pointwise_mul_pallas(fa, fb, ctx, interpret=_interpret())
    return out.reshape(lead + (ctx.n,))


def fused_rotate_hadamard(polys, tw, f0, f1, ctx: PrimeCtx, *,
                          use_pallas=None):
    """Cached re-rank core for one prime: slot twiddle rotate -> Hadamard
    against both query components -> slot/chunk mod-sum.

    polys: (B, num_ct, cpt*chunks, N) slot-major gathered cache rows;
    tw: (cpt, N) NTT-domain monomial diagonals; f0/f1: (B, chunks, N) query
    NTTs.  Returns (acc0, acc1), each (B, num_ct, N).  The Pallas path runs
    the whole thing as one kernel (grid batch x result-ct); the fallback is
    a single jitted XLA composition — both bit-identical to the cold
    pack-then-NTT pipeline.
    """
    use_pallas = _resolve(use_pallas)
    if not use_pallas:
        return _fused_rotate_hadamard_ref(polys, tw, f0, f1, ctx)
    return _fused.fused_rerank_pallas(polys, tw, f0, f1, ctx,
                                      interpret=_interpret())


def fused_rotate_hadamard_intt(polys, tw, f0, f1, ctx: PrimeCtx, *,
                               use_pallas=None):
    """`fused_rotate_hadamard` with the per-prime inverse NTT absorbed: the
    returned (acc0, acc1) are coefficient-domain result-ciphertext
    components, (B, num_ct, N) each.

    On the Pallas path the inverse butterfly network runs inside the same
    kernel while the accumulator tile is still VMEM-resident (no HBM
    round-trip between accumulate and iNTT — the batch-8 Hadamard/iNTT
    bottleneck); the fallback composes the jitted XLA reference fused op
    with the reference inverse NTT.  Both paths run the exact same integer
    ops as the staged rotate/Hadamard + `ntt_inv` pipeline, so all three
    are bit-identical.
    """
    use_pallas = _resolve(use_pallas)
    if not use_pallas:
        return _fused_rotate_hadamard_intt_ref(polys, tw, f0, f1, ctx)
    return _fused.fused_rerank_intt_pallas(polys, tw, f0, f1, ctx,
                                           interpret=_interpret())


def negacyclic_mul(a, b, ctx: PrimeCtx, *, use_pallas=None):
    """a * b in Z_q[X]/(X^N + 1)."""
    use_pallas = _resolve(use_pallas)
    fa = ntt_fwd(a, ctx, use_pallas=use_pallas)
    fb = ntt_fwd(b, ctx, use_pallas=use_pallas)
    return ntt_inv(pointwise_mul(fa, fb, ctx, use_pallas=use_pallas), ctx,
                   use_pallas=use_pallas)


__all__ = ["ntt_fwd", "ntt_inv", "pointwise_mul", "fused_rotate_hadamard",
           "fused_rotate_hadamard_intt", "negacyclic_mul"]
