"""Vectorized Paillier: the object path's batch twin (ROADMAP RNS item).

`crypto/paillier.py` is the paper-faithful per-lane implementation: pure
Python bignums, one interpreter-level modmul at a time.  This module computes
the *same integers* — wire-byte identical ciphertexts given the same rng,
bit-exact decryptions — but moves the modular arithmetic onto the RNS
Montgomery kernels in `repro.kernels.bignum`, batched over every lane of a
serve group at once.  Division of labor per stage:

  encrypt    r^n for all dims of a query in one windowed-modexp kernel
             (blinding r drawn host-side in the object path's exact draw
             order, so ciphertext bytes match under a shared rng)
  score      the big one: per-(lane, dim) windowed power tables for the
             query ciphertexts and their inverses, then per window position
             one gathered [lanes, k', dims] multiply + a product tree over
             dims — replacing k'·dims·popcount interpreter modmuls with a
             handful of fused array ops (candidate scalars are 15-bit
             fixed-point, so 3 windows of 5 bits cover them)
  decrypt    batched c^lambda, host L-function/mu finish

Query-ciphertext inverses (for negative fixed-point scalars) use Montgomery's
batch-inversion trick: one modular inverse plus 3 multiplies per element,
instead of one ~50us extended-gcd per (lane, dim).

Fallback: keys whose n^2 needs more residue channels than the compiled
budget (`bignum.ref.MAX_CHANNELS`, e.g. 1024-bit keys at the default
budget) transparently take the object path per lane; `counters` records
which path served each lane so tests and benches can assert the boundary.
Lanes of *different* key sizes within one batch are grouped by channel
count and each cohort runs as one kernel call.

Exactness: the channels need exact float64 products and sums below 2^53,
which only the CPU gives (`repro.kernels.exact_float64`; a TPU emulates
float64).  Elsewhere every vectorized entry point raises `InexactDevice`
instead of serving wrong integers.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.crypto import paillier as pai
from repro.kernels import exact_float64
from repro.kernels.bignum import ops, ref

SCORE_WINDOW = 5    # 15-bit fixed-point scalars -> at most 3 window positions
EXP_WINDOW = 4      # dense (key-sized) exponents: n for blinding, lambda


class InexactDevice(RuntimeError):
    """The default device cannot run the float64 channel arithmetic
    exactly, so the vectorized tier refuses rather than serve wrong
    integers."""


def _require_exact() -> None:
    if not exact_float64():
        raise InexactDevice(
            f"the {jax.default_backend()} device does not run the float64 "
            f"RNS channels exactly; vectorized Paillier refuses to serve "
            f"on it")


# Which path served each lane-call: tests and the fallback-boundary bench
# assert on these.  reset_counters() between measurements.
counters = {"vectorized": 0, "object": 0}


def reset_counters() -> None:
    counters["vectorized"] = 0
    counters["object"] = 0


def fits(pub: pai.PaillierPublicKey) -> bool:
    """True when this key's n^2 is inside the compiled channel budget."""
    return ref.fits(pub.n_sq)


@functools.lru_cache(maxsize=64)
def _ctx(n_sq: int) -> ref.RnsModulus:
    return ref.for_modulus(n_sq)


def _draw_r(pub: pai.PaillierPublicKey,
            rng: Optional[np.random.Generator]) -> int:
    # Exact replica of paillier.encrypt's draw loop: consuming the same
    # rng stream in the same order is what makes wire bytes match.
    while True:
        r = pai._randbelow(pub.n, rng)
        if r and math.gcd(r, pub.n) == 1:
            return r


def _batch_modinv(values: Sequence[int], modulus: int) -> List[int]:
    """Montgomery batch inversion: one extended-gcd + 3 muls per element."""
    prefix = [1]
    for v in values:
        prefix.append(prefix[-1] * v % modulus)
    inv = pow(prefix[-1], -1, modulus)
    out = [0] * len(values)
    for i in range(len(values) - 1, -1, -1):
        out[i] = inv * prefix[i] % modulus
        inv = inv * values[i] % modulus
    return out


def _consts(ctxs: Sequence[ref.RnsModulus], batch_ndim: int) -> dict:
    return ops.make_consts(ctxs[0].system, list(ctxs), batch_ndim)


def _to_rns_mont(ctxs: Sequence[ref.RnsModulus],
                 rows: Sequence[Sequence[int]]) -> np.ndarray:
    """Per-lane int rows -> stacked Montgomery-form channel array
    [lanes, len(row), channels]."""
    out = [ref.to_rns(c, [v * c.system.M % c.modulus for v in row])
           for c, row in zip(ctxs, rows)]
    return np.stack(out)


@functools.partial(jax.jit, static_argnames=("window",))
def _exp_kernel(base, digits, C, window):
    table = ops.pow_table(base, C, window)
    acc = ops.mont_exp_digits(table, digits, C, window)
    return ops.mont_mul(acc, C["plain_one"], C)


@functools.partial(jax.jit, static_argnames=("wscore", "wexp"))
def _score_kernel(q, qinv, digits, signs, rbase, rdigits, C2, C3,
                  wscore, wexp):
    """One serve group's encrypted re-rank.

    q/qinv: [L, D, C] Montgomery query cts (+inverses); digits: [L, K, D, P]
    window digits of |k| (most-significant first); signs: [L, K, D] int32
    (1 = negative scalar -> inverse table); rbase: [L, K, C] Montgomery
    blinding bases; rdigits: [L, K, Pn] digits of each lane's n.
    Returns demontgomerized [L, K, C] score ciphertext channels.

    Candidates run through a `lax.scan` in chunks so the gather + product
    tree working set stays cache-sized instead of materializing the full
    [L, k', dims, C] block per window position (~20% on a 1-core host).
    """
    table = jnp.concatenate(
        [ops.pow_table(q, C2, wscore), ops.pow_table(qinv, C2, wscore)], 0)
    nlanes, kprime = digits.shape[0], digits.shape[1]
    chunk = next(c for c in (8, 4, 2, 1) if kprime % c == 0)

    def one_chunk(dig, sgn):                                  # [L, c, D, ...]
        acc = jnp.broadcast_to(C2["one"], (nlanes, chunk, table.shape[-1]))
        for p in range(dig.shape[-1]):
            acc = ops.square_n(acc, C2, wscore)
            idx = dig[..., p] + sgn * (1 << wscore)           # [L, c, D]
            g = jnp.take_along_axis(table[:, :, None], idx[None, ..., None],
                                    axis=0)[0]                # [L, c, D, C]
            acc = ops.mont_mul(acc, ops.product_reduce(g, C3), C2)
        return acc

    dch = jnp.moveaxis(
        digits.reshape(nlanes, -1, chunk, *digits.shape[2:]), 1, 0)
    sch = jnp.moveaxis(
        signs.reshape(nlanes, -1, chunk, signs.shape[-1]), 1, 0)
    _, accs = jax.lax.scan(
        lambda _, ds: (None, one_chunk(*ds)), None, (dch, sch))
    acc = jnp.moveaxis(accs, 0, 1).reshape(nlanes, kprime, -1)
    blind = ops.mont_exp_digits(ops.pow_table(rbase, C2, wexp),
                                rdigits, C2, wexp)
    return ops.mont_mul(ops.mont_mul(acc, blind, C2), C2["plain_one"], C2)


def _from_channels(ctx: ref.RnsModulus, arr: np.ndarray) -> List[int]:
    return [v % ctx.modulus for v in ref.from_rns(ctx, arr)]


def encrypt_vector(pub: pai.PaillierPublicKey, e: np.ndarray,
                   rng: Optional[np.random.Generator] = None) -> list:
    """Drop-in for `paillier.encrypt_vector`: same bytes, batched r^n."""
    e = np.asarray(e, np.float64)
    if not fits(pub) or len(e) == 0:
        counters["object"] += 1
        return pai.encrypt_vector(pub, e, rng)
    _require_exact()
    counters["vectorized"] += 1
    ms = pai.encode_vector(e, pub.n)     # one batched call, not per-lane
    rs = [_draw_r(pub, rng) for _ in ms]
    ctx = _ctx(pub.n_sq)
    with jax.enable_x64(True):
        C = _consts([ctx], batch_ndim=2)
        base = _to_rns_mont([ctx], [rs])
        ndig = ops.to_digits([pub.n], EXP_WINDOW)
        digits = np.ascontiguousarray(np.broadcast_to(
            ndig[:, None, :], (1, len(ms), ndig.shape[-1])))
        rn = np.asarray(_exp_kernel(base, digits, C, EXP_WINDOW))
    rn_ints = _from_channels(ctx, rn[0])
    return [(1 + m * pub.n) % pub.n_sq * x % pub.n_sq
            for m, x in zip(ms, rn_ints)]


def encrypted_scores_batch(
        pubs: Sequence[pai.PaillierPublicKey],
        enc_queries: Sequence[Sequence[int]],
        cands: Sequence[np.ndarray],
        rngs: Optional[Sequence[Optional[np.random.Generator]]] = None,
) -> List[list]:
    """Batched `paillier.encrypted_scores` across lanes.

    ``cands[i]`` is lane i's [k', dims] candidate block (same shape across
    lanes — the serve group contract).  ``rngs`` supplies per-lane blinding
    randomness in the object path's draw order; None draws from `secrets`.
    Oversized keys fall back per lane.  Returns per-lane ciphertext lists.
    """
    nlanes = len(pubs)
    if rngs is None:
        rngs = [None] * nlanes
    out: List[Optional[list]] = [None] * nlanes
    if any(fits(pub) for pub in pubs):
        _require_exact()

    # Blinding must be drawn lane-by-lane in candidate order *before* any
    # cohort regrouping, to consume each lane's stream exactly as the
    # object path would.
    cohorts: dict = {}
    for i, pub in enumerate(pubs):
        kprime = np.asarray(cands[i]).shape[0]
        if not fits(pub):
            counters["object"] += 1
            out[i] = pai.encrypted_scores(pub, enc_queries[i], cands[i],
                                          rng=rngs[i])
            continue
        counters["vectorized"] += 1
        rs = [_draw_r(pub, rngs[i]) for _ in range(kprime)]
        cohorts.setdefault(ref.num_channels(pub.n_sq), []).append((i, rs))

    for s, members in cohorts.items():
        lanes = [i for i, _ in members]
        ctxs = [_ctx(pubs[i].n_sq) for i in lanes]
        blk = np.stack([np.asarray(cands[i], np.float64) for i in lanes])
        ks = np.rint(blk * (1 << pai.FRAC_BITS)).astype(np.int64)
        signs = (ks < 0).astype(np.int32)
        kabs = np.abs(ks)
        npos = max(1, -(-int(kabs.max()).bit_length() // SCORE_WINDOW))
        shifts = SCORE_WINDOW * np.arange(npos - 1, -1, -1)
        digits = ((kabs[..., None] >> shifts)
                  & ((1 << SCORE_WINDOW) - 1)).astype(np.int32)
        qs = [list(enc_queries[i]) for i in lanes]
        qinvs = [_batch_modinv(row, ctx.modulus)
                 for row, ctx in zip(qs, ctxs)]
        ndig = ops.to_digits([pubs[i].n for i in lanes], EXP_WINDOW)
        kprime = blk.shape[1]
        with jax.enable_x64(True):
            res = _score_kernel(
                _to_rns_mont(ctxs, qs),
                _to_rns_mont(ctxs, qinvs),
                digits, signs,
                _to_rns_mont(ctxs, [rs for _, rs in members]),
                np.ascontiguousarray(np.broadcast_to(
                    ndig[:, None, :], (len(lanes), kprime, ndig.shape[-1]))),
                _consts(ctxs, batch_ndim=2), _consts(ctxs, batch_ndim=3),
                SCORE_WINDOW, EXP_WINDOW)
            res = np.asarray(res)
        for j, i in enumerate(lanes):
            out[i] = _from_channels(ctxs[j], res[j])
    return out


def decrypt_scores_batch(sks: Sequence[pai.PaillierSecretKey],
                         enc_lists: Sequence[Sequence[int]],
                         ) -> List[np.ndarray]:
    """Batched `paillier.decrypt_scores`: c^lambda in one kernel per cohort,
    L-function + centered fixed-point decode on the host (bit-exact)."""
    nlanes = len(sks)
    out: List[Optional[np.ndarray]] = [None] * nlanes
    if any(fits(sk.pub) and len(enc) for sk, enc in zip(sks, enc_lists)):
        _require_exact()
    cohorts: dict = {}
    for i, sk in enumerate(sks):
        if not fits(sk.pub) or len(enc_lists[i]) == 0:
            counters["object"] += 1
            out[i] = pai.decrypt_scores(sk, enc_lists[i])
            continue
        counters["vectorized"] += 1
        cohorts.setdefault(ref.num_channels(sk.pub.n_sq), []).append(i)

    for s, lanes in cohorts.items():
        ctxs = [_ctx(sks[i].pub.n_sq) for i in lanes]
        kprime = len(enc_lists[lanes[0]])
        ldig = ops.to_digits([sks[i].lam for i in lanes], EXP_WINDOW)
        with jax.enable_x64(True):
            res = np.asarray(_exp_kernel(
                _to_rns_mont(ctxs, [enc_lists[i] for i in lanes]),
                np.ascontiguousarray(np.broadcast_to(
                    ldig[:, None, :], (len(lanes), kprime, ldig.shape[-1]))),
                _consts(ctxs, batch_ndim=2), EXP_WINDOW))
        for j, i in enumerate(lanes):
            sk = sks[i]
            xs = _from_channels(ctxs[j], res[j])
            ms = [(x - 1) // sk.pub.n * sk.mu % sk.pub.n for x in xs]
            out[i] = np.asarray(
                [pai._decode(m, sk.pub.n, 2 * pai.FRAC_BITS) for m in ms],
                np.float64)
    return out


__all__ = ["InexactDevice", "fits", "encrypt_vector", "encrypted_scores_batch",
           "decrypt_scores_batch", "counters", "reset_counters",
           "SCORE_WINDOW", "EXP_WINDOW"]
