"""TPU-native RNS-RLWE additively homomorphic encryption ("BFV-lite").

This is the hardware adaptation of the paper's PHE (Module 2a).  Paillier is
bignum modexp — hostile to the MXU/VPU — so we use the RLWE analogue of "PHE
with ct+ct and ct*plain": BFV without relinearisation.

Scheme (symmetric key; the user is both encryptor and decryptor):

  ring      R_q = Z_q[X]/(X^N + 1),  q = q_0 q_1 q_2  (RNS, ~20-bit NTT primes)
  secret    s ternary in {-1, 0, 1}^N
  enc(m)    c0 = a*s + e + Delta*m,  c1 = a;   a ~ U(R_q), e ~ CBD(eta)
  dec(ct)   m = round(t/q * centered(c0 - c1*s)) mod t
  add       componentwise;  ct (x) p = (c0*p, c1*p)  for plaintext p in R

Encrypted inner products use negacyclic-convolution packing: the fixed-point
query chunk is the plaintext of a ciphertext; each candidate chunk is packed
*reversed* into a plain polynomial at block offset o_b, so coefficient
o_b + chunk - 1 of ct (x) p is exactly <query_chunk, cand_chunk>.  Chunks of
dimension > chunk_size are summed homomorphically.  Multiple candidates share
one ciphertext via block stride (N/stride candidates per result ciphertext).

The per-document half of that packing (reverse placement + forward NTT) is
request-invariant, so it is hoisted into an NTT-domain candidate cache
built once per index; at request time a candidate's block offset is realized
as a pointwise monomial-twiddle rotate in the NTT domain (bit-identical to
fresh packing — see CandidateCache / encrypted_scores_cached_batch).  Two
cache layouts share one packed pool: the dense `CandidateCache` keeps the
whole corpus resident in device memory, and the corpus-scale
`ShardedCandidateCache` partitions it into host-pooled shards with an
LRU-pinned device-resident hot set and per-request on-demand gather of only
the k' selected candidates' rows.  Shard admission is frequency-aware and
asynchronous by default — a background admitter performs the shard-sized
host->device copy off the request path and atomically swaps the shard in,
admitting only shards whose decayed touch counter reaches a threshold (see
CandidateCacheConfig; `async_admission=False` restores the deterministic
synchronous first-touch LRU for replay tests).

Correctness budget (validated in `RlweParams.validate`): every *extraction*
coefficient of m*p is an inner product of unit-norm vectors scaled by
Delta_q*Delta_c (Cauchy-Schwarz) and therefore < t/2; mod-t wraps can only
occur at garbage coefficients, which decryption treats coefficient-locally.
Noise after plain-mult is ||e||_inf * ||p||_1 <= eta * C * Delta_c * sqrt(cs),
far below q / (2t).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import threading
import time
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs

from repro.crypto import modring
from repro.crypto.modring import PrimeCtx
from repro.kernels import resolve_use_pallas
from repro.kernels.ntt import ops as ntt_ops
from repro.kernels.ntt import ref as ntt_ref


@dataclasses.dataclass(frozen=True, eq=False)
class RlweParams:
    n_poly: int = 4096          # ring dimension N
    num_primes: int = 3         # RNS primes (~20 bits each)
    t_bits: int = 28            # plaintext modulus t = 2^t_bits
    scale_q_bits: int = 13      # query fixed-point scale  Delta_q = 2^13
    scale_c_bits: int = 13      # candidate fixed-point scale Delta_c = 2^13
    eta: int = 8                # CBD noise parameter, |e| <= eta
    chunk: int = 1024           # dot-product chunk size (<= n_poly)

    def __post_init__(self):
        assert self.n_poly % self.chunk == 0
        self.validate()

    @functools.cached_property
    def primes(self) -> tuple:
        return modring.find_ntt_primes(2 * self.n_poly, self.num_primes)

    @functools.cached_property
    def ctxs(self) -> tuple:
        return tuple(PrimeCtx.build(q, self.n_poly) for q in self.primes)

    @functools.cached_property
    def big_q(self) -> int:
        return math.prod(self.primes)

    @property
    def t(self) -> int:
        return 1 << self.t_bits

    @functools.cached_property
    def delta(self) -> int:
        return self.big_q // self.t

    @property
    def scale_q(self) -> int:
        return 1 << self.scale_q_bits

    @property
    def scale_c(self) -> int:
        return 1 << self.scale_c_bits

    def stride(self, n_dim: int) -> int:
        """Block stride: extraction at o_b + chunk - 1 must clear the previous
        block's span o_b + chunk - 1 + (chunk_used - 1)."""
        return self.chunk if n_dim <= self.chunk else 2 * self.chunk

    def cands_per_ct(self, n_dim: int) -> int:
        return self.n_poly // self.stride(n_dim)

    def num_chunks(self, n_dim: int) -> int:
        return -(-n_dim // self.chunk)

    def validate(self) -> None:
        # plaintext range: extraction coefficients bounded by Delta_q*Delta_c
        # (unit-norm Cauchy-Schwarz) + quantization slop < t/2.
        assert (1 << (self.scale_q_bits + self.scale_c_bits)) * 1.1 < self.t / 2, \
            "plaintext scales overflow t"
        # noise: after plain-mult and chunk-summing,
        #   |noise| <= eta * cands_per_ct_max * Delta_c * sqrt(chunk) * chunks_max
        worst = (self.eta * (self.n_poly // self.chunk) * self.scale_c
                 * math.isqrt(self.chunk) * 4)
        assert 2 * self.t * worst < self.big_q, "noise budget exceeded"

    def ciphertext_bytes(self, packed_bits: int = 20) -> int:
        """Wire size of one ciphertext (2 components, RNS, bit-packed)."""
        return 2 * self.num_primes * self.n_poly * packed_bits // 8


@dataclasses.dataclass(frozen=True, eq=False)
class RlweSecretKey:
    params: RlweParams
    s: np.ndarray          # (N,) int8 ternary
    s_ntt: jnp.ndarray     # (P, N) int32 — NTT(s) per prime


@dataclasses.dataclass(frozen=True, eq=False)
class QueryCiphertext:
    """Encrypted, chunked query embedding: (chunks, P, N) int32 per component."""
    c0: jnp.ndarray
    c1: jnp.ndarray
    n_dim: int


@dataclasses.dataclass(frozen=True, eq=False)
class PackedCandidates:
    """NTT-domain packed candidate plaintexts.

    polys: (num_ct, chunks, P, N) int32; candidate i lives in result ct
    i // cands_per_ct at extraction coefficient (i % cands_per_ct) * stride
    + chunk - 1.
    """
    polys: jnp.ndarray
    n_dim: int
    num_cands: int


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["c0", "c1"],
                   meta_fields=["n_dim", "num_cands"])
@dataclasses.dataclass(frozen=True, eq=False)
class ScoreCiphertexts:
    """Encrypted inner products: (num_ct, P, N) int32 per component.  A
    pytree of its two arrays, so ``jax.block_until_ready`` reaches them."""
    c0: jnp.ndarray
    c1: jnp.ndarray
    n_dim: int
    num_cands: int


@dataclasses.dataclass(frozen=True, eq=False)
class ScoreCiphertextBatch:
    """B stacked score ciphertexts: (B, num_ct, P, N) int32 per component.

    The serving path keeps this stacked form end-to-end (scoring ->
    decryption) so no per-lane device work happens; `lane`/`lanes` hand out
    per-request views for the wire messages."""
    c0: jnp.ndarray
    c1: jnp.ndarray
    n_dim: int
    num_cands: int

    @property
    def batch(self) -> int:
        return self.c0.shape[0]

    def lane(self, b: int) -> ScoreCiphertexts:
        return ScoreCiphertexts(c0=self.c0[b], c1=self.c1[b],
                                n_dim=self.n_dim, num_cands=self.num_cands)

    def lanes(self) -> list:
        return [self.lane(b) for b in range(self.batch)]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _to_rns(values: np.ndarray, params: RlweParams) -> np.ndarray:
    """Signed int64 (..., N) -> RNS int32 (P, ..., N)."""
    out = [np.mod(values, q).astype(np.int32) for q in params.primes]
    return np.stack(out, axis=0)


def _cbd(rng: np.random.Generator, eta: int, n: int) -> np.ndarray:
    a = rng.integers(0, 2, size=(eta, n)).sum(axis=0)
    b = rng.integers(0, 2, size=(eta, n)).sum(axis=0)
    return (a - b).astype(np.int64)


def keygen(params: RlweParams, rng: np.random.Generator) -> RlweSecretKey:
    s = rng.integers(-1, 2, size=(params.n_poly,)).astype(np.int8)
    s_rns = _to_rns(s.astype(np.int64), params)  # (P, N)
    s_ntt = jnp.stack([
        ntt_ops.ntt_fwd(jnp.asarray(s_rns[i]), ctx)
        for i, ctx in enumerate(params.ctxs)
    ])
    return RlweSecretKey(params=params, s=s, s_ntt=s_ntt)


def _fixed_point(e: np.ndarray, scale: int) -> np.ndarray:
    return np.rint(np.asarray(e, np.float64) * scale).astype(np.int64)


# ---------------------------------------------------------------------------
# user side: encrypt / decrypt
# ---------------------------------------------------------------------------

def encrypt_query(sk: RlweSecretKey, e: np.ndarray,
                  rng: np.random.Generator) -> QueryCiphertext:
    """Encrypt a unit-norm query embedding of any dimension (chunked)."""
    p = sk.params
    n_dim = e.shape[-1]
    chunks = p.num_chunks(n_dim)
    ints = _fixed_point(e, p.scale_q)
    c0s, c1s = [], []
    for c in range(chunks):
        m = np.zeros(p.n_poly, np.int64)
        seg = ints[c * p.chunk:(c + 1) * p.chunk]
        m[: len(seg)] = seg
        # signed (centered) encoding: Delta*m mod q, computed per RNS prime.
        # An unsigned mod-t lift would add a Delta*t*w term that explodes
        # under plain-mult; signed encoding keeps Dec(ct (x) p) = m*p exactly
        # while |(m*p)_j| < t/2 at the coefficients we read.
        err = _cbd(rng, p.eta, p.n_poly)
        c0_p, c1_p = [], []
        for i, ctx in enumerate(p.ctxs):
            a = rng.integers(0, ctx.q, size=(p.n_poly,)).astype(np.int32)
            dm = (int(p.delta % ctx.q) * np.mod(m, ctx.q)) % ctx.q  # int64 safe
            a_s = ntt_ops.ntt_inv(
                ntt_ops.pointwise_mul(
                    ntt_ops.ntt_fwd(jnp.asarray(a), ctx), sk.s_ntt[i], ctx),
                ctx)
            c0 = (np.asarray(a_s).astype(np.int64) + err + dm) % ctx.q
            c0_p.append(c0.astype(np.int32))
            c1_p.append(a)
        c0s.append(np.stack(c0_p))
        c1s.append(np.stack(c1_p))
    return QueryCiphertext(
        c0=jnp.asarray(np.stack(c0s)), c1=jnp.asarray(np.stack(c1s)), n_dim=n_dim)


def decrypt_rns(params: RlweParams, s_ntt: jnp.ndarray, c0: jnp.ndarray,
                c1: jnp.ndarray, *, use_pallas=None) -> np.ndarray:
    """RNS phase of decryption: d = c0 - c1*s per prime.

    ``c0``/``c1`` are (..., P, N); ``s_ntt`` broadcasts against the leading
    dims of NTT(c1) — pass (P, N) for one key or (B, 1, P, N)-style stacks
    for a batch of per-tenant keys.  Returns int64 (..., P, N).
    """
    d_p = []
    for i, ctx in enumerate(params.ctxs):
        f1 = ntt_ops.ntt_fwd(c1[..., i, :], ctx, use_pallas=use_pallas)
        sb = jnp.broadcast_to(s_ntt[..., i, :], f1.shape)
        c1s = ntt_ops.ntt_inv(
            ntt_ops.pointwise_mul(f1, sb, ctx, use_pallas=use_pallas), ctx,
            use_pallas=use_pallas)
        d = modring.mod_sub(c0[..., i, :], c1s, ctx.q)
        d_p.append(np.asarray(d).astype(np.int64))
    return np.stack(d_p, axis=-2)


def extract_scores(params: RlweParams, d_rns: np.ndarray, n_dim: int,
                   num_cands: int) -> np.ndarray:
    """CRT-reconstruct the extraction coefficients of d_rns (num_ct, P, N)
    (Python bignums) -> float scores (num_cands,)."""
    p = params
    stride = p.stride(n_dim)
    cpt = p.cands_per_ct(n_dim)
    g = [p.big_q // q for q in p.primes]
    h = [pow(gi % qi, -1, qi) for gi, qi in zip(g, p.primes)]
    scale = float(p.scale_q * p.scale_c)
    out = np.zeros(num_cands, np.float64)
    for cand in range(num_cands):
        ct_i, slot = divmod(cand, cpt)
        coeff = slot * stride + p.chunk - 1
        big = 0
        for i, qi in enumerate(p.primes):
            big += int(d_rns[ct_i, i, coeff]) * g[i] * h[i]
        big %= p.big_q
        if big > p.big_q // 2:
            big -= p.big_q
        val = round(big * p.t / p.big_q)  # noise removal
        # centered mod t
        val = ((val + p.t // 2) % p.t) - p.t // 2
        out[cand] = val / scale
    return out


def decrypt_scores(sk: RlweSecretKey, res: ScoreCiphertexts) -> np.ndarray:
    """Decrypt packed inner products -> float scores (len num_cands)."""
    d_rns = decrypt_rns(sk.params, sk.s_ntt, res.c0, res.c1)
    return extract_scores(sk.params, d_rns, res.n_dim, res.num_cands)


# ---------------------------------------------------------------------------
# cloud side: NTT-domain candidate cache (build once, serve many)
# ---------------------------------------------------------------------------

def params_key(params: RlweParams) -> tuple:
    """Value identity of an RlweParams: two instances with the same key are
    interchangeable for packing/scoring (primes derive from n_poly+num_primes)."""
    return (params.n_poly, params.num_primes, params.t_bits,
            params.scale_q_bits, params.scale_c_bits, params.eta, params.chunk)


@dataclasses.dataclass(frozen=True, eq=False)
class CandidateCache:
    """Per-document NTT-domain plaintexts, packed once at index-build time.

    Row ``polys[d]`` holds document d's chunks, each reverse-packed at slot
    0 (p[chunk-1-j] = seg[j]) and forward-NTT'd per prime, flattened in
    (chunk, prime, coefficient) order: (num_docs, chunks*P*N) int32 — 4*P*N
    bytes per chunk per document (48 KiB/doc/chunk at the default N=4096,
    P=3).  One row per document is the layout the TPU gather reads in place
    (an embedding lookup); a (num_docs, chunks, P, N) device array makes
    XLA relayout the whole pool on every scoring call.  `host_pool` gives
    the (num_docs, chunks, P, N) view.

    Realizing document d at slot s of a result ciphertext is a pointwise
    multiply by ``twiddles[:, s]``, the NTT-domain
    diagonal of the monomial X^{s*stride}: the slot-0 support [0, chunk)
    never crosses X^N + 1 for s < cands_per_ct, so X^{s*stride} * base is
    exactly the polynomial the cold packer would have built, and the NTT is
    a ring isomorphism — cached scoring is bit-identical to fresh packing.

    ``stride``/``cands_per_ct``/``num_chunks`` are hoisted out of the hot
    loops; `check_compatible` rejects reuse under different ``RlweParams``
    (the build-once/serve-many contract is per (index, params-value) pair).
    """
    params: RlweParams
    polys: jnp.ndarray             # (num_docs, chunks*P*N) int32, NTT domain
    twiddles: jnp.ndarray          # (P, cands_per_ct, N) int32, NTT(X^{s*stride})
    n_dim: int
    num_docs: int
    stride: int
    cands_per_ct: int
    num_chunks: int

    @property
    def nbytes(self) -> int:
        return int(self.polys.size) * 4

    def host_pool(self) -> np.ndarray:
        """Host view/copy of the packed pool as (num_docs, chunks, P, N),
        memoized on first use so every sharded re-view
        (`shard_candidate_cache`) shares ONE host array no matter how many
        configs consume it — and dense-only callers never pay for it.
        Zero-copy on the CPU backend; one D2H on accelerators.
        """
        pool = self.__dict__.get("_host_pool")
        if pool is None:
            # frozen dataclass: memoize via __dict__ (cached_property style)
            pool = self.__dict__["_host_pool"] = np.asarray(
                self.polys).reshape(self.num_docs, self.num_chunks,
                                    self.params.num_primes,
                                    self.params.n_poly)
        return pool

    def check_compatible(self, params: RlweParams, n_dim=None) -> None:
        _check_cache_compatible(self, params, n_dim)


def _cache_geometry(params: RlweParams, n_dim: int) -> tuple:
    """(chunks, stride, cands_per_ct) with the int32-accumulator check the
    scoring kernels rely on (slot/chunk accumulators sum cpt*chunks raw
    int32 terms in [0, q) before one Barrett reduction)."""
    chunks = params.num_chunks(n_dim)
    stride = params.stride(n_dim)
    cpt = params.cands_per_ct(n_dim)
    assert cpt * chunks * (params.primes[0] - 1) < 2**31, \
        "cpt*chunks too large for the int32 accumulator"
    return chunks, stride, cpt


def _pack_corpus_ntt(params: RlweParams, emb: np.ndarray) -> np.ndarray:
    """The corpus half of negacyclic packing, hoisted offline: every
    document's chunks reverse-packed at slot 0 and forward-NTT'd per prime.
    Returns the host pool (num_docs, chunks, P, N) int32 — the single source
    of truth backing both the dense and the sharded candidate cache."""
    num_docs, n_dim = emb.shape
    chunks, _, _ = _cache_geometry(params, n_dim)
    # pack + NTT in document blocks: peak transient host memory is one
    # ~64 MiB int64 staging buffer (plus its RNS copy), not 3x the corpus
    block = max(1, (1 << 23) // (chunks * params.n_poly))
    parts = []
    for lo in range(0, num_docs, block):
        seg_emb = emb[lo:lo + block]
        ints = _fixed_point(seg_emb, params.scale_c)      # (b, n_dim)
        polys = np.zeros((len(seg_emb), chunks, params.n_poly), np.int64)
        for c in range(chunks):
            seg = ints[:, c * params.chunk:(c + 1) * params.chunk]
            polys[:, c, params.chunk - 1 - np.arange(seg.shape[1])] = seg
        rns = _to_rns(polys, params)                      # (P, b, chunks, N)
        parts.append(np.stack([
            np.asarray(ntt_ops.ntt_fwd(jnp.asarray(rns[i]), ctx))
            for i, ctx in enumerate(params.ctxs)
        ], axis=2))                                       # (b, chunks, P, N)
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


def _slot_twiddles(params: RlweParams, n_dim: int) -> jnp.ndarray:
    """NTT-domain diagonals of the slot monomials X^{s*stride}: (P, cpt, N)."""
    _, stride, cpt = _cache_geometry(params, n_dim)
    mono = np.zeros((cpt, params.n_poly), np.int64)
    mono[np.arange(cpt), np.arange(cpt) * stride] = 1
    mrns = _to_rns(mono, params)                          # (P, cpt, N)
    return jnp.stack([
        ntt_ops.ntt_fwd(jnp.asarray(mrns[i]), ctx)
        for i, ctx in enumerate(params.ctxs)
    ])                                                    # (P, cpt, N)


def _dense_cache(params: RlweParams, pool: np.ndarray, n_dim: int,
                 twiddles=None) -> CandidateCache:
    """The dense cache over a host pool (num_docs, chunks, P, N): one
    host->device copy of the pool as rows (num_docs, chunks*P*N), a free
    reshape of the host array."""
    chunks, stride, cpt = _cache_geometry(params, n_dim)
    if twiddles is None:
        twiddles = _slot_twiddles(params, n_dim)
    return CandidateCache(params=params,
                          polys=jnp.asarray(pool.reshape(pool.shape[0], -1)),
                          twiddles=twiddles, n_dim=n_dim,
                          num_docs=pool.shape[0], stride=stride,
                          cands_per_ct=cpt, num_chunks=chunks)


def build_candidate_cache(params: RlweParams,
                          embeddings: np.ndarray) -> CandidateCache:
    """Precompute the NTT-domain plaintexts of every document (slot 0) plus
    the per-slot monomial twiddles.  One vectorized host pack + one forward
    NTT per prime for the whole corpus; after this the server's encrypted
    workload touches only per-request data.  The whole pool lives dense in
    device memory — at corpus scale use `build_sharded_candidate_cache`."""
    emb = np.asarray(embeddings)
    return _dense_cache(params, _pack_corpus_ntt(params, emb), emb.shape[1])


# ---------------------------------------------------------------------------
# cloud side: sharded HBM-resident candidate cache (corpus scale)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CandidateCacheConfig:
    """Knobs for the sharded candidate cache (hashable: `FlatIndex` memoizes
    one cache per (RlweParams value, config) pair).

    shard_docs / num_shards   partition of the corpus into contiguous
                              document ranges (specify one; ``shard_docs``
                              wins).  Default: 8 shards.
    max_resident_bytes        device-memory budget for LRU-pinned hot shards.
                              ``None`` = unbounded (every admitted shard
                              stays resident), ``0`` = stream-only (no
                              admission; each request gathers its k' rows
                              from the host pool on demand).
    pin_on_access             allow admission of missed shards to device
                              residency (subject to the budget and the
                              admission policy below).  ``False`` keeps the
                              resident set fixed to whatever `pin` loaded.
    async_admission           True (default): admissions run on a background
                              admitter thread — the shard-sized host->device
                              copy happens off the request path and the
                              shard is atomically swapped into the resident
                              set when the copy completes; `gather` never
                              blocks on an in-flight admission (it streams
                              the k' rows from the host pool until the shard
                              is resident).  False: the deterministic legacy
                              mode — synchronous, unconditional first-touch
                              admission inside `gather`, preserving the
                              bit-identical LRU traces the determinism tests
                              pin down.
    admit_threshold           (async mode) admit a shard only on its
                              ``admit_threshold``-th touch within the decay
                              window — the default 2 ("second touch") keeps
                              one-shot uniform sweeps from churning the
                              resident set while repeat traffic still admits
                              after one repeat.
    admit_window              (async mode) decayed-counter window: every
                              ``admit_window`` counted shard touches, all
                              touch counters are halved (and sub-1 counters
                              dropped), so stale popularity ages out.
                              ``None`` (default) resolves at build time to
                              ``max(8, num_shards)`` — the window that
                              separates the regimes: traffic spread
                              uniformly over all shards touches each shard
                              about once per window, so its counter decays
                              before the second touch and nothing is ever
                              admitted (zero churn), while traffic
                              concentrated on a minority of shards
                              re-touches them several times per window and
                              admits after one repeat.
    max_pending_admissions    (async mode) bound on queued background
                              admissions; further admission requests are
                              dropped (and counted) until the queue drains,
                              so a regime shift cannot build an unbounded
                              copy backlog.

    One config for both regimes: with async admission the admission cost is
    off the request path, so the default policy serves *skewed* traffic
    (hot shards admitted after one repeat touch, then gathered device-side)
    and *uniform* traffic (requests stream from the host pool; background
    churn is bounded by the queue cap) without per-regime tuning —
    `benchmarks/rlwe_bench.py` gates both regimes under this one default.
    Stream-only (``max_resident_bytes=0``) and operator placement
    (``pin_on_access=False`` + explicit `ShardedCandidateCache.pin`) remain
    available for fixed deployments.
    """
    shard_docs: Optional[int] = None
    num_shards: Optional[int] = None
    max_resident_bytes: Optional[int] = None
    pin_on_access: bool = True
    async_admission: bool = True
    admit_threshold: int = 2
    admit_window: Optional[int] = None
    max_pending_admissions: int = 4

    def __post_init__(self):
        # CLI-reachable knobs: fail loudly at construction, not mid-serve
        if self.admit_threshold < 1:
            raise ValueError(
                f"admit_threshold must be >= 1, got {self.admit_threshold}")
        if self.admit_window is not None and self.admit_window < 1:
            raise ValueError(
                f"admit_window must be >= 1, got {self.admit_window}")
        if self.max_pending_admissions < 1:
            raise ValueError(f"max_pending_admissions must be >= 1, got "
                             f"{self.max_pending_admissions}")

    def resolve_admit_window(self, num_shards: int) -> int:
        """``None`` -> the regime-separating auto window (see class doc)."""
        if self.admit_window is not None:
            return self.admit_window
        return max(8, num_shards)

    def resolve_shard_docs(self, num_docs: int) -> int:
        if self.shard_docs is not None:
            if self.shard_docs <= 0:        # CLI-reachable: fail loudly
                raise ValueError(
                    f"shard_docs must be positive, got {self.shard_docs}")
            return self.shard_docs
        n_shards = self.num_shards if self.num_shards is not None else 8
        if n_shards <= 0:
            raise ValueError(f"num_shards must be positive, got {n_shards}")
        return max(1, -(-num_docs // n_shards))


@dataclasses.dataclass(eq=False)
class ShardedCandidateCache:
    """Capacity-aware sharded view of the NTT-domain candidate pool.

    The per-document plaintexts (the same (doc, chunk, P, N) int32 rows a
    dense `CandidateCache` would hold on device) live in a flat host pool
    partitioned into contiguous document shards; document d maps to shard
    ``d // shard_docs``, local row ``d % shard_docs`` — assigned at index
    build, aligned with `FlatIndex` row sharding.  Device memory holds only

      * an LRU set of *pinned hot shards* bounded by ``max_resident_bytes``
        (repeat tenants hitting the same shard gather device-side), and
      * the per-request gather buffer: the k' selected candidates' chunks,
        fetched on demand (`jnp.take` from a resident shard, or a host-side
        row gather of just those k' rows for a non-resident shard).

    Gathered rows are the exact pool rows the dense cache would `jnp.take`,
    so sharded scoring is bit-identical to the dense cache and to cold
    packing regardless of the resident set, eviction history, admission
    policy, or any in-flight background admission.

    Admission policy (see `CandidateCacheConfig`): in the default *async*
    mode a missed shard is only a candidate for residency — its decayed
    touch counter must reach ``admit_threshold`` (2nd touch by default)
    before an admission is enqueued to the background admitter thread,
    which stages the host->device copy into a private buffer and atomically
    swaps the shard into the resident set under the cache lock.  `gather`
    never waits: until the swap it streams the selected rows from the host
    pool (double-buffered admission — the request path and the in-flight
    copy never share a buffer).  `prefetch` lets the serving engine enqueue
    those admissions as soon as the batched top-k' candidate ids are known,
    so the copy overlaps the request's encrypt/Hadamard compute; a prefetch
    counts the touch, and the request's own `gather` of the same ids does
    not double-count it.

    With ``async_admission=False`` eviction/admission is the deterministic
    legacy mode: shards are admitted synchronously on first touch in access
    order (MRU at the back of an OrderedDict), evicted oldest-first
    whenever the resident set exceeds the budget; a re-accessed shard is
    re-pinned the same way.  ``hits``/``misses`` count shard-group lookups
    (one per distinct shard touched by a gather), not individual documents.
    """
    params: RlweParams
    twiddles: jnp.ndarray          # (P, cpt, N) — same as the dense cache
    n_dim: int
    num_docs: int
    stride: int
    cands_per_ct: int
    num_chunks: int
    shard_docs: int
    pool: np.ndarray               # host (num_docs, chunks, P, N) backing store
    shards: list                   # views into ``pool``, <=shard_docs docs each
    epoch: int = 0                 # corpus epoch (bumped by `ingest_tail`)
    max_resident_bytes: Optional[int] = None
    pin_on_access: bool = True
    async_admission: bool = True
    admit_threshold: int = 2
    admit_window: int = 64
    max_pending_admissions: int = 4
    sharding: Optional[object] = None   # jax.sharding.Sharding for pinned shards
    _resident: collections.OrderedDict = dataclasses.field(
        default_factory=collections.OrderedDict, repr=False)
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    gathered_bytes: int = 0        # host->device on-demand row traffic
    peak_resident_bytes: int = 0
    admissions: int = 0            # completed admissions (sync + async + pin)
    async_admissions: int = 0      # ... of which completed on the admitter
    prefetches: int = 0            # shard touches recorded via `prefetch`
    admit_enqueued: int = 0        # admissions handed to the admitter
    admit_dropped: int = 0         # admission requests dropped (queue full)
    admit_failed: int = 0          # background copies that raised (e.g. a
                                   # device allocation failure); next touch
                                   # retries
    last_admit_error: Optional[str] = None
    policy_deferrals: int = 0      # touches below admit_threshold (no admit)

    def __post_init__(self):
        # Admitter state lives outside the dataclass fields: one lock
        # guards the resident set + policy counters; the condition wakes
        # the (lazily started) admitter thread and `flush` waiters.
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: collections.deque = collections.deque()
        self._inflight: set = set()       # enqueued or mid-copy shard ids
        self._touch_counts: dict = {}     # shard id -> decayed touch count
        self._touches = 0                 # counted touches since build
        self._prefetched: set = set()     # touches already counted upstream
        self._worker: Optional[threading.Thread] = None
        self._closed = False
        self._admit_hook = None           # test seam: called(s) pre-swap
        self._ingest_hook = None          # test seam: called(self) pre-publish
        # shard boundary table: shard s owns docs [starts[s], starts[s+1]).
        # Uniform `d // shard_docs` at build; `ingest_tail` appends
        # boundaries, so the mapping stays valid for ragged tail shards.
        self._starts = np.cumsum(
            [0] + [s.shape[0] for s in self.shards])[:-1]
        self.ingests = 0                  # tail shards appended since build
        # telemetry sink (repro.obs): the serving engine re-binds these
        # every dispatch via `set_trace_context` — the cache is index-
        # memoized and may outlive any one engine.  Spans record only
        # shard ids and byte/row counts (redaction enforced by the
        # tracer); the admitter thread records on its own "admitter"
        # track, parented to the batch whose prefetch/gather enqueued it.
        self.tracer = obs.NULL_TRACER
        self._trace_batch: Optional[int] = None

    def set_trace_context(self, tracer, batch_id: Optional[int]) -> None:
        """Bind the tracer + current batch id for spans this cache emits
        (including admissions completed later on the admitter thread)."""
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        self._trace_batch = batch_id

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    @property
    def pool_nbytes(self) -> int:
        """Total host pool size — what the dense cache would pin on device."""
        return sum(s.nbytes for s in self.shards)

    def host_pool(self) -> np.ndarray:
        """The full packed pool including any ingested tail shards — the
        original backing array when the cache never grew, else one
        concatenated copy (re-view/densify paths only; the request path
        always reads per-shard)."""
        with self._lock:
            shards = list(self.shards)
        if self.pool.shape[0] == sum(s.shape[0] for s in shards):
            return self.pool
        return np.concatenate(shards, axis=0)

    def _resident_bytes_locked(self) -> int:
        return sum(int(v.size) * 4 for v in self._resident.values())

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._resident_bytes_locked()

    @property
    def resident_shards(self) -> tuple:
        """Resident shard ids, LRU -> MRU (deterministic under a fixed
        access trace; asserted in tests)."""
        with self._lock:
            return tuple(self._resident.keys())

    def stats(self) -> dict:
        # one lock scope: the admitter swaps/evicts concurrently, so every
        # _resident-derived value must come from the same snapshot
        with self._lock:
            resident_bytes = self._resident_bytes_locked()
            resident_shards = tuple(self._resident.keys())
            pending = len(self._inflight)
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "gathered_bytes": self.gathered_bytes,
                "resident_bytes": resident_bytes,
                "peak_resident_bytes": self.peak_resident_bytes,
                "pool_bytes": self.pool_nbytes,
                "num_shards": self.num_shards,
                "resident_shards": resident_shards,
                "admissions": self.admissions,
                "async_admissions": self.async_admissions,
                "prefetches": self.prefetches,
                "admit_enqueued": self.admit_enqueued,
                "admit_dropped": self.admit_dropped,
                "admit_failed": self.admit_failed,
                "last_admit_error": self.last_admit_error,
                "policy_deferrals": self.policy_deferrals,
                "pending_admissions": pending,
                "epoch": self.epoch,
                "ingests": self.ingests}

    def check_compatible(self, params: RlweParams, n_dim=None) -> None:
        _check_cache_compatible(self, params, n_dim)

    def shard_of(self, doc_id: int) -> int:
        return int(np.searchsorted(self._starts, int(doc_id),
                                   side="right")) - 1

    def _shard_ids(self, flat: np.ndarray) -> np.ndarray:
        """Validated document ids -> shard ids (the single id->shard
        mapping `gather` and `prefetch` share).  Boundary-table lookup:
        identical to ``flat // shard_docs`` for the uniform build layout,
        and still correct for ragged tail shards appended by
        `ingest_tail` (ids below an earlier epoch's num_docs always map
        the same way — the table only ever grows)."""
        if flat.size and (flat.min() < 0 or flat.max() >= self.num_docs):
            # negative ids would alias shards[-1] via Python indexing and
            # silently gather the wrong document; fail loudly instead
            raise IndexError(
                f"candidate ids must be in [0, {self.num_docs}); got "
                f"[{flat.min()}, {flat.max()}]")
        return np.searchsorted(self._starts, flat, side="right") - 1

    def pin(self, shard_id: int) -> None:
        """Explicitly admit a shard to device residency (LRU position =
        most-recent); evicts oldest shards if over budget.  Always
        synchronous — operator placement wants the shard resident on
        return, whatever the background policy."""
        with self.tracer.span("cache_pin", shard=int(shard_id),
                              batch_id=self._trace_batch):
            with self._lock:
                self._admit_locked(int(shard_id))

    # -- admission: shared swap-in (caller holds the lock) -------------------

    def _fits_budget(self, s: int) -> bool:
        return (self.max_resident_bytes is None
                or self.shards[s].nbytes <= self.max_resident_bytes)

    def _swap_in_locked(self, s: int, arr) -> None:
        """Atomically install a staged device copy of shard ``s``: evict
        LRU-first down to budget, then publish.  The staging buffer was
        built outside the lock (and, on the async path, off the request
        thread), so residency never exceeds the budget and `gather` never
        observes a half-copied shard — it streams from the host pool until
        this swap."""
        nbytes = self.shards[s].nbytes
        if self.max_resident_bytes is not None:
            while (self._resident_bytes_locked() + nbytes
                   > self.max_resident_bytes):
                evicted, _ = self._resident.popitem(last=False)
                self.evictions += 1
                # tracer has its own lock and never takes the cache lock,
                # so recording under the cache lock cannot deadlock
                self.tracer.event("cache_evict", shard=int(evicted),
                                  batch_id=self._trace_batch)
        self._resident[s] = arr
        self.admissions += 1
        self.peak_resident_bytes = max(self.peak_resident_bytes,
                                       self._resident_bytes_locked())

    def _stage_copy(self, s: int):
        arr = jnp.asarray(self.shards[s])
        if self.sharding is not None:
            arr = jax.device_put(arr, self.sharding)
        return arr

    def _admit_locked(self, s: int) -> None:
        """Legacy synchronous admission (also `pin`): copy + swap inline."""
        if s in self._resident:
            self._resident.move_to_end(s)
            return
        if not self._fits_budget(s):
            return                  # shard alone exceeds the budget: stream
        with self.tracer.span("cache_admit", shard=int(s),
                              batch_id=self._trace_batch,
                              bytes=int(self.shards[s].nbytes)):
            self._swap_in_locked(s, self._stage_copy(s))

    # -- admission: frequency-aware policy + background admitter -------------

    def _touch_locked(self, s: int) -> None:
        """Count one (non-prefetched) touch of a missed shard and enqueue a
        background admission when the decayed counter reaches the
        threshold."""
        if self.max_resident_bytes == 0 or not self._fits_budget(s):
            return                  # stream-only / oversized: never admit
        self._touches += 1
        if self._touches % self.admit_window == 0:
            # decay: halve every counter each window; sub-1 entries age out
            self._touch_counts = {k: v / 2
                                  for k, v in self._touch_counts.items()
                                  if v >= 1.0}
        count = self._touch_counts.get(s, 0.0) + 1.0
        self._touch_counts[s] = count
        if count < self.admit_threshold:
            self.policy_deferrals += 1
            return
        if s in self._resident or s in self._inflight:
            return
        if len(self._queue) >= self.max_pending_admissions:
            self.admit_dropped += 1   # counter keeps it eligible next touch
            return
        self._touch_counts.pop(s, None)
        self._inflight.add(s)
        # the triggering batch rides along so the admitter's span is
        # parented to the request that earned the admission
        self._queue.append((s, self._trace_batch))
        self.admit_enqueued += 1
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._admit_worker, name="shard-admitter", daemon=True)
            self._worker.start()
        self._cv.notify_all()

    def _admit_worker(self) -> None:
        """Background admitter: drain the queue one shard at a time.  The
        H2D copy (`_stage_copy` + block_until_ready) runs outside the lock —
        the request path keeps streaming from the host pool meanwhile — and
        only the final swap takes the lock.  An idle worker retires after a
        timeout (releasing its reference to the cache and pool); the next
        enqueue respawns one — `_touch_locked` checks under the same lock,
        so no admission can fall between a retiring and a spawning worker."""
        while True:
            with self._cv:
                if not self._queue and not self._closed:
                    self._cv.wait(timeout=60.0)
                if not self._queue:       # closed, or idled out: retire
                    self._worker = None
                    return
                s, parent = self._queue.popleft()
            tracer = self.tracer
            t0 = tracer.clock() if tracer.enabled else 0.0
            err = None
            try:
                hook = self._admit_hook   # test seam: delay/observe the copy
                if hook is not None:
                    hook(s)
                arr = self._stage_copy(s)
                jax.block_until_ready(arr)   # the copy, off-request-path
            except Exception as e:        # noqa: BLE001 — a failed copy must
                arr, err = None, repr(e)  # not strand flush()/later admits
            swapped = False
            with self._cv:
                self._inflight.discard(s)
                if arr is None:           # counted; next touch retries
                    self.admit_failed += 1
                    self.last_admit_error = err
                elif s in self._resident:
                    self._resident.move_to_end(s)
                elif self._fits_budget(s) and self.max_resident_bytes != 0:
                    self._swap_in_locked(s, arr)
                    self.async_admissions += 1
                    swapped = True
                self._cv.notify_all()     # wake flush()
            if tracer.enabled:
                # span covers the whole off-path admission (staged copy +
                # swap) on the admitter's own track, so the timeline shows
                # it overlapping the request's encrypt/score compute
                tracer.record("cache_admit", t0, tracer.clock(),
                              track="admitter", batch_id=parent,
                              shard=int(s),
                              bytes=int(self.shards[s].nbytes),
                              ok=swapped)

    def prefetch(self, ids) -> int:
        """Serving-engine admission hook: note the shard touches implied by
        a batch's top-k' candidate ``ids`` and enqueue any admissions the
        policy grants *now*, before the request's encrypt/Hadamard work, so
        the background copy overlaps compute.  The subsequent `gather` of
        the same ids does not double-count these touches.  Returns the
        number of shards whose touch was recorded.  No-op (returns 0) when
        admission is disabled or in synchronous legacy mode."""
        if not (self.pin_on_access and self.async_admission):
            return 0
        flat = np.asarray(ids).reshape(-1)
        shard_ids = self._shard_ids(flat)
        if flat.size == 0:
            return 0
        tracer = self.tracer
        t0 = tracer.clock() if tracer.enabled else 0.0
        touched = 0
        with self._lock:
            # one fresh credit set per batch: stale credits from a previous
            # prefetch (e.g. a shard that became resident before its gather)
            # must not suppress future miss accounting
            self._prefetched = set()
            for s in np.unique(shard_ids):
                s = int(s)
                if s in self._resident:
                    continue          # gather will hit; nothing to admit
                self._touch_locked(s)
                self._prefetched.add(s)
                self.prefetches += 1
                touched += 1
        if tracer.enabled:
            tracer.record("cache_prefetch", t0, tracer.clock(),
                          batch_id=self._trace_batch, shards=touched)
        return touched

    def flush(self, timeout: float = 60.0) -> None:
        """Block until every enqueued admission has completed (or timed
        out).  Request paths never need this — it exists so tests and
        benchmarks can observe the converged resident set."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._queue or self._inflight:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"shard admissions did not drain within {timeout}s "
                        f"({len(self._queue)} queued, "
                        f"{len(self._inflight)} in flight)")
                self._cv.wait(remaining)

    def close(self) -> None:
        """Stop the admitter thread (pending admissions still complete).
        Idempotent; the cache remains usable afterwards in streaming mode
        (a later admission restarts the worker)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        worker = self._worker
        if worker is not None and worker.is_alive():
            worker.join(timeout=60.0)
        with self._cv:
            self._closed = False      # allow lazy restart

    def ingest_tail(self, rows: np.ndarray, *, epoch: int) -> None:
        """Streaming ingestion: append newly packed docs as a *tail shard*
        and stamp the cache with the new corpus ``epoch``.

        ``rows`` is the `_pack_corpus_ntt` output for the new documents —
        fully materialized before this call, like the admitter's staged
        copy, so the publish under the cache lock is a pointer append: a
        concurrent `gather` observes either the pre-ingest shard table or
        the complete tail shard, never a half-swapped one.  Ids below the
        previous ``num_docs`` keep their shard mapping (the boundary table
        only grows), which is what makes a fixed-epoch replay bit-identical
        while ingestion runs.  The tail shard then rides the *existing*
        atomic admission path to device residency — enqueued to the
        background admitter (staged copy off-lock, `_swap_in_locked`
        publish); until that swap, gathers stream it from the host like
        any other non-resident shard."""
        rows = np.ascontiguousarray(rows)
        want = (self.num_chunks, self.params.num_primes, self.params.n_poly)
        if rows.ndim != 4 or rows.shape[1:] != want:
            raise ValueError(
                f"tail shard rows must be (m, {want[0]}, {want[1]}, "
                f"{want[2]}), got {rows.shape}")
        if rows.shape[0] == 0:
            return
        hook = self._ingest_hook    # test seam: interleave pre-publish
        if hook is not None:
            hook(self)
        with self._cv:
            if epoch <= self.epoch:
                raise ValueError(
                    f"stale ingest epoch {epoch} (cache is at "
                    f"{self.epoch})")
            s = len(self.shards)
            self.shards.append(rows)
            self._starts = np.append(self._starts, self.num_docs)
            self.num_docs += rows.shape[0]
            self.epoch = epoch
            self.ingests += 1
            # warm the tail through the normal admission machinery
            if (self.pin_on_access and self.async_admission
                    and self.max_resident_bytes != 0
                    and self._fits_budget(s)
                    and len(self._queue) < self.max_pending_admissions):
                self._inflight.add(s)
                self._queue.append((s, self._trace_batch))
                self.admit_enqueued += 1
                if self._worker is None or not self._worker.is_alive():
                    self._worker = threading.Thread(
                        target=self._admit_worker, name="shard-admitter",
                        daemon=True)
                    self._worker.start()
            self._cv.notify_all()

    def gather(self, ids) -> jnp.ndarray:
        """On-demand gather of the selected candidates' cached rows:
        (B, num_cands) document ids -> (B, num_cands, chunks, P, N) device
        array, touching only those k' documents per lane.

        Ids are grouped by shard; resident shards gather device-side
        (`jnp.take`), non-resident shards gather just the selected rows from
        the host pool.  When ``pin_on_access``, a miss feeds the admission
        policy: synchronous first-touch LRU admission in legacy mode
        (``async_admission=False``), else a counted touch that may enqueue a
        background admission — the gather itself never waits on the copy."""
        ids = np.asarray(ids)
        assert ids.ndim == 2, "ids must be (B, num_cands)"
        bsz, nc = ids.shape
        tracer = self.tracer
        t0 = tracer.clock() if tracer.enabled else 0.0
        h0, m0, g0 = self.hits, self.misses, self.gathered_bytes
        flat = ids.reshape(-1)
        shard_ids = self._shard_ids(flat)
        local = flat - self._starts[shard_ids]
        order = np.argsort(shard_ids, kind="stable")      # group by shard
        uniq, starts = np.unique(shard_ids[order], return_index=True)
        bounds = np.append(starts, order.size)
        parts = []
        for s, lo, hi in zip(uniq, bounds[:-1], bounds[1:]):
            s = int(s)
            sel = order[lo:hi]
            loc = local[sel]
            with self._lock:                  # vs admitter swap/evict
                dev = self._resident.get(s)
                if dev is not None:
                    self.hits += 1
                    self._resident.move_to_end(s)         # LRU touch
                    self._prefetched.discard(s)   # credit no longer needed
                elif self.pin_on_access:
                    if not self.async_admission:
                        self._admit_locked(s)
                    elif s in self._prefetched:
                        self._prefetched.discard(s)   # counted at prefetch
                    else:
                        self._touch_locked(s)
            if dev is not None:
                rows = jnp.take(dev, jnp.asarray(loc), axis=0)
            else:
                self.misses += 1
                rows = jnp.asarray(self.shards[s][loc])   # host row gather
                self.gathered_bytes += int(rows.size) * 4
            parts.append(rows)
        g = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
        inv = np.empty_like(order)
        inv[order] = np.arange(order.size)                # undo the grouping
        g = jnp.take(g, jnp.asarray(inv), axis=0)
        out = g.reshape(bsz, nc, self.num_chunks,
                        self.params.num_primes, self.params.n_poly)
        if tracer.enabled:
            tracer.record("cache_gather", t0, tracer.clock(),
                          batch_id=self._trace_batch, lanes=int(bsz),
                          num_cands=int(nc), shards=int(uniq.size),
                          hits=self.hits - h0, misses=self.misses - m0,
                          bytes=self.gathered_bytes - g0)
        return out


def _check_cache_compatible(cache, params: RlweParams, n_dim=None) -> None:
    if params_key(params) != params_key(cache.params):
        raise ValueError(
            f"candidate cache was built for RlweParams "
            f"{params_key(cache.params)} but scoring uses "
            f"{params_key(params)}; rebuild the cache for these params")
    if n_dim is not None and n_dim != cache.n_dim:
        raise ValueError(
            f"candidate cache packs n_dim={cache.n_dim} but the query "
            f"has n_dim={n_dim}")


def _shard_pool(params: RlweParams, pool: np.ndarray, n_dim: int,
                config: CandidateCacheConfig,
                sharding=None, twiddles=None,
                epoch: int = 0) -> ShardedCandidateCache:
    num_docs = pool.shape[0]
    chunks, stride, cpt = _cache_geometry(params, n_dim)
    shard_docs = config.resolve_shard_docs(num_docs)
    shards = [pool[lo:lo + shard_docs]                    # views, no copy
              for lo in range(0, num_docs, shard_docs)]
    if twiddles is None:
        twiddles = _slot_twiddles(params, n_dim)
    return ShardedCandidateCache(
        params=params, twiddles=twiddles, n_dim=n_dim,
        num_docs=num_docs, stride=stride, cands_per_ct=cpt,
        num_chunks=chunks, shard_docs=shard_docs, pool=pool, shards=shards,
        epoch=epoch,
        max_resident_bytes=config.max_resident_bytes,
        pin_on_access=config.pin_on_access,
        async_admission=config.async_admission,
        admit_threshold=config.admit_threshold,
        admit_window=config.resolve_admit_window(len(shards)),
        max_pending_admissions=config.max_pending_admissions,
        sharding=sharding)


def build_sharded_candidate_cache(
        params: RlweParams, embeddings: np.ndarray, *,
        config: Optional[CandidateCacheConfig] = None,
        sharding=None) -> ShardedCandidateCache:
    """Pack + forward-NTT the corpus once (host pool) and partition it into
    shards.  ``sharding`` optionally places pinned shards with a
    `jax.sharding.Sharding` (mesh row axes — see `FlatIndex`)."""
    emb = np.asarray(embeddings)
    config = config if config is not None else CandidateCacheConfig()
    pool = _pack_corpus_ntt(params, emb)
    return _shard_pool(params, pool, emb.shape[1], config, sharding)


def shard_candidate_cache(cache,
                          config: Optional[CandidateCacheConfig] = None,
                          sharding=None) -> ShardedCandidateCache:
    """Re-view an existing cache's pool (dense `CandidateCache` or another
    `ShardedCandidateCache`) as a sharded cache under a new config, without
    re-packing — bit-identity between the views is true by construction,
    and the packed pool (the expensive pack + forward-NTT product) is built
    once per params value no matter how many configs consume it."""
    config = config if config is not None else CandidateCacheConfig()
    pool = cache.host_pool()       # includes any ingested tail shards
    return _shard_pool(cache.params, pool, cache.n_dim, config, sharding,
                       twiddles=cache.twiddles,
                       epoch=getattr(cache, "epoch", 0))


def densify_candidate_cache(cache: ShardedCandidateCache) -> CandidateCache:
    """Dense device-resident view of a sharded cache's pool (one
    host->device copy, no re-pack; the host pool stays shared)."""
    pool = cache.host_pool()       # includes any ingested tail shards
    dense = _dense_cache(cache.params, pool, cache.n_dim, cache.twiddles)
    dense.__dict__["_host_pool"] = pool         # keep the pool shared
    return dense


def _scores_pipeline(c0, c1, g, twiddles, ctxs, cpt, pad, use_pallas):
    """Traced body shared by the dense and pre-gathered entry points: zero
    padding for the last result ciphertext's empty slots, then per prime a
    query forward NTT and the fused rotate -> Hadamard -> slot/chunk mod-sum
    -> inverse NTT (one kernel per prime on the Pallas path; the per-prime
    loop unrolls at trace time, and the RNS stack of coefficient-domain
    outputs is assembled in the same jit — no host round-trips)."""
    bsz, num_cands = g.shape[0], g.shape[1]
    chunks, n = c0.shape[1], c0.shape[-1]
    if pad:                  # empty slots of the last result ciphertext
        g = jnp.concatenate(
            [g, jnp.zeros((bsz, pad) + g.shape[2:], jnp.int32)], axis=1)
    num_ct = (num_cands + pad) // cpt
    outs0, outs1 = [], []
    for i, ctx in enumerate(ctxs):
        f0 = ntt_ops.ntt_fwd(c0[:, :, i, :], ctx, use_pallas=use_pallas)
        f1 = ntt_ops.ntt_fwd(c1[:, :, i, :], ctx, use_pallas=use_pallas)
        polys_i = g[..., i, :].reshape(bsz, num_ct, cpt * chunks, n)
        acc0, acc1 = ntt_ops.fused_rotate_hadamard_intt(
            polys_i, twiddles[i], f0, f1, ctx, use_pallas=use_pallas)
        outs0.append(acc0)
        outs1.append(acc1)
    return jnp.stack(outs0, axis=2), jnp.stack(outs1, axis=2)


@functools.partial(jax.jit,
                   static_argnames=("ctxs", "cpt", "pad", "use_pallas"))
def _cached_scores(c0, c1, polys, ids, twiddles, ctxs, cpt, pad, use_pallas):
    """Whole-batch dense-cache scoring in ONE compiled call: the cache
    gather, last-ct zero padding, and the per-prime loop all live in a
    single trace, so the full gather -> rotate -> Hadamard -> slot/chunk
    mod-sum -> iNTT pipeline runs without host round-trips.  ``polys`` is
    the pool as rows (num_docs, chunks*P*N), gathered in place; the k'
    gathered rows are unflattened to the query's (chunks, P, N).
    ``use_pallas`` is static: the same trace routes through the fused
    Pallas kernel or the jitted XLA references (one layout/padding
    implementation for both, so the bit-identity contract holds by
    construction)."""
    bsz, num_cands = ids.shape
    g = jnp.take(polys, ids.reshape(-1), axis=0)        # (B*nc, chunks*P*N)
    g = g.reshape((bsz, num_cands) + c0.shape[1:])      # (B, nc, chunks, P, N)
    return _scores_pipeline(c0, c1, g, twiddles, ctxs, cpt, pad, use_pallas)


@functools.partial(jax.jit,
                   static_argnames=("ctxs", "cpt", "pad", "use_pallas"))
def _gathered_scores(c0, c1, g, twiddles, ctxs, cpt, pad, use_pallas):
    """Sharded-cache scoring: same compiled pipeline as `_cached_scores`
    minus the dense gather — ``g`` (B, nc, chunks, P, N) was assembled by
    `ShardedCandidateCache.gather` (a stateful LRU, so it cannot live inside
    the jit).  Identical trace below the gather => identical bits."""
    return _scores_pipeline(c0, c1, g, twiddles, ctxs, cpt, pad, use_pallas)


def encrypted_scores_cached_batch(params: RlweParams,
                                  q_cts: Sequence[QueryCiphertext],
                                  cache, cand_ids,
                                  *, use_pallas=None) -> ScoreCiphertextBatch:
    """Batched ct (x) p against cached NTT-domain candidates (``cache`` is a
    dense `CandidateCache` or a `ShardedCandidateCache`).

    Per-request work: one gather of k' cached rows per lane (device `take`
    for the dense cache; shard-grouped on-demand gather for the sharded
    cache), then per prime one fused rotate -> Hadamard -> slot/chunk
    mod-sum -> inverse NTT (Pallas kernel or the jitted XLA fallback) plus
    2*chunks query forward NTTs.  No per-candidate host loop and no
    candidate forward NTTs — those moved to the cache build.  Bit-identical
    to pack_candidates_batch + encrypted_scores_batch (same decrypted
    scores, same wire bytes), for either cache kind.
    """
    ids = np.asarray(cand_ids)
    assert ids.ndim == 2, "cand_ids must be (B, num_cands)"
    bsz, num_cands = ids.shape
    assert len(q_cts) == bsz
    cache.check_compatible(params, q_cts[0].n_dim)
    cpt = cache.cands_per_ct
    num_ct = -(-num_cands // cpt)
    pad = num_ct * cpt - num_cands
    c0 = jnp.stack([q.c0 for q in q_cts])                 # (B, chunks, P, N)
    c1 = jnp.stack([q.c1 for q in q_cts])
    use_pallas = resolve_use_pallas(use_pallas)
    if isinstance(cache, ShardedCandidateCache):
        g = cache.gather(ids)                 # (B, nc, chunks, P, N)
        all0, all1 = _gathered_scores(
            c0, c1, g, cache.twiddles, params.ctxs, cpt, pad, use_pallas)
    else:
        all0, all1 = _cached_scores(
            c0, c1, cache.polys, jnp.asarray(ids), cache.twiddles,
            params.ctxs, cpt, pad, use_pallas)
    return ScoreCiphertextBatch(c0=all0, c1=all1, n_dim=cache.n_dim,
                                num_cands=num_cands)


def encrypted_scores_cached(params: RlweParams, q_ct: QueryCiphertext,
                            cache, cand_ids,
                            *, use_pallas=None) -> ScoreCiphertexts:
    """Cached ct (x) p for one query (the B=1 slice of the batch version)."""
    res = encrypted_scores_cached_batch(
        params, [q_ct], cache, np.asarray(cand_ids)[None],
        use_pallas=use_pallas)
    return res.lane(0)


# ---------------------------------------------------------------------------
# cloud side: pack candidates, encrypted scoring
# ---------------------------------------------------------------------------

def pack_candidates_batch(params: RlweParams,
                          cands: np.ndarray) -> jnp.ndarray:
    """Pack (B, num_cands, n_dim) candidate rows -> (B, num_ct, chunks, P, N)
    NTT-domain plaintexts.  The reversed placement (p[o + chunk-1 - j] =
    seg[j]) vectorizes over B; the NTT batches all leading dims."""
    bsz, num_cands, n_dim = cands.shape
    chunks = params.num_chunks(n_dim)
    stride = params.stride(n_dim)
    cpt = params.cands_per_ct(n_dim)
    num_ct = -(-num_cands // cpt)
    ints = _fixed_point(cands, params.scale_c)  # (B, num_cands, n_dim)

    polys = np.zeros((bsz, num_ct, chunks, params.n_poly), np.int64)
    for cand in range(num_cands):
        ct_i, slot = divmod(cand, cpt)
        o = slot * stride
        for c in range(chunks):
            seg = ints[:, cand, c * params.chunk:(c + 1) * params.chunk]
            idx = o + params.chunk - 1 - np.arange(seg.shape[1])
            polys[:, ct_i, c, idx] = seg
    rns = _to_rns(polys, params)  # (P, B, num_ct, chunks, N)
    return jnp.stack([
        ntt_ops.ntt_fwd(jnp.asarray(rns[i]), ctx)
        for i, ctx in enumerate(params.ctxs)
    ], axis=3)  # (B, num_ct, chunks, P, N) — stays on device


def pack_candidates(params: RlweParams, cands: np.ndarray) -> PackedCandidates:
    """Pack candidate embeddings (num_cands, n_dim) into NTT-domain
    plaintexts (the B=1 slice of the batch packer — one source of truth)."""
    num_cands, n_dim = cands.shape
    polys = pack_candidates_batch(params, np.asarray(cands)[None])[0]
    return PackedCandidates(polys=polys, n_dim=n_dim, num_cands=num_cands)


@functools.partial(jax.jit, static_argnames=("ctxs",))
def _scores_batch_ref(c0, c1, packed, ctxs):
    """Whole-batch fallback scoring in ONE compiled call: the per-prime loop
    unrolls at trace time (no host round-trips between primes) and the
    homomorphic chunk-sum is a vectorized mod-sum, not a Python loop."""
    outs0, outs1 = [], []
    for i, ctx in enumerate(ctxs):
        f0 = ntt_ref.ntt_fwd_ref(c0[:, :, i, :], ctx)   # (B, chunks, N)
        f1 = ntt_ref.ntt_fwd_ref(c1[:, :, i, :], ctx)
        pk = packed[:, :, :, i, :]                      # (B, num_ct, chunks, N)
        prod0 = modring.mod_mul(pk, f0[:, None], ctx.q, ctx.mu)
        prod1 = modring.mod_mul(pk, f1[:, None], ctx.q, ctx.mu)
        acc0 = modring.mod_sum(prod0, ctx.q, ctx.mu, axis=2)
        acc1 = modring.mod_sum(prod1, ctx.q, ctx.mu, axis=2)
        outs0.append(ntt_ref.ntt_inv_ref(acc0, ctx))
        outs1.append(ntt_ref.ntt_inv_ref(acc1, ctx))
    return jnp.stack(outs0, axis=2), jnp.stack(outs1, axis=2)


def encrypted_scores_batch_stacked(params: RlweParams,
                                   q_cts: Sequence[QueryCiphertext],
                                   packed: jnp.ndarray, num_cands: int,
                                   n_dim: int, *,
                                   use_pallas=None) -> ScoreCiphertextBatch:
    """Batched ct (x) p: B query ciphertexts against (B, num_ct, chunks, P,
    N) packed candidates, chunk-summed in the NTT domain — one NTT dispatch
    per prime for the whole batch.

    This is the cloud's entire encrypted workload: 2 * chunks forward NTTs
    per query (amortized over all candidates), one Hadamard modmul per
    (lane, result-ct, chunk, component, prime), and 2 inverse NTTs per
    result ct.  The result stays stacked on device.
    """
    c0 = jnp.stack([q.c0 for q in q_cts])  # (B, chunks, P, N)
    c1 = jnp.stack([q.c1 for q in q_cts])
    if not resolve_use_pallas(use_pallas):
        all0, all1 = _scores_batch_ref(c0, c1, packed, params.ctxs)
        return ScoreCiphertextBatch(c0=all0, c1=all1, n_dim=n_dim,
                                    num_cands=num_cands)
    c0_out, c1_out = [], []
    for i, ctx in enumerate(params.ctxs):
        f0 = ntt_ops.ntt_fwd(c0[:, :, i, :], ctx, use_pallas=True)
        f1 = ntt_ops.ntt_fwd(c1[:, :, i, :], ctx, use_pallas=True)
        pk = packed[:, :, :, i, :]                 # (B, num_ct, chunks, N)
        f0b = jnp.broadcast_to(f0[:, None], pk.shape)
        f1b = jnp.broadcast_to(f1[:, None], pk.shape)
        prod0 = ntt_ops.pointwise_mul(pk, f0b, ctx, use_pallas=True)
        prod1 = ntt_ops.pointwise_mul(pk, f1b, ctx, use_pallas=True)
        acc0 = modring.mod_sum(prod0, ctx.q, ctx.mu, axis=2)
        acc1 = modring.mod_sum(prod1, ctx.q, ctx.mu, axis=2)
        c0_out.append(ntt_ops.ntt_inv(acc0, ctx, use_pallas=True))
        c1_out.append(ntt_ops.ntt_inv(acc1, ctx, use_pallas=True))
    return ScoreCiphertextBatch(
        c0=jnp.stack(c0_out, axis=2), c1=jnp.stack(c1_out, axis=2),
        n_dim=n_dim, num_cands=num_cands)


def encrypted_scores_batch(params: RlweParams,
                           q_cts: Sequence[QueryCiphertext],
                           packed: jnp.ndarray, num_cands: int, n_dim: int,
                           *, use_pallas=None) -> list:
    """List-of-lanes view of `encrypted_scores_batch_stacked` (lanes are
    views of one stacked device array, no per-lane crypto work)."""
    return encrypted_scores_batch_stacked(
        params, q_cts, packed, num_cands, n_dim,
        use_pallas=use_pallas).lanes()


def encrypted_scores(params: RlweParams, q_ct: QueryCiphertext,
                     packed: PackedCandidates, *,
                     use_pallas=None) -> ScoreCiphertexts:
    """ct (x) p per candidate block (the B=1 slice of the batch version)."""
    assert q_ct.n_dim == packed.n_dim
    return encrypted_scores_batch(
        params, [q_ct], packed.polys[None], num_cands=packed.num_cands,
        n_dim=packed.n_dim, use_pallas=use_pallas)[0]


def decrypt_scores_batch(sks: Sequence[RlweSecretKey], cts,
                         *, use_pallas=None) -> list:
    """Decrypt B score ciphertexts under B (distinct) tenant keys with one
    NTT dispatch per prime; CRT extraction stays per-lane (host bignums).

    ``cts`` is either a list of ScoreCiphertexts or a ScoreCiphertextBatch —
    the stacked form skips the per-lane restack entirely."""
    params = sks[0].params
    if isinstance(cts, ScoreCiphertextBatch):
        c0, c1 = cts.c0, cts.c1
        meta = [(cts.n_dim, cts.num_cands)] * cts.batch
    else:
        c0 = jnp.stack([c.c0 for c in cts])        # (B, num_ct, P, N)
        c1 = jnp.stack([c.c1 for c in cts])
        meta = [(c.n_dim, c.num_cands) for c in cts]
    s_ntt = jnp.stack([sk.s_ntt for sk in sks])[:, None]  # (B, 1, P, N)
    d_rns = decrypt_rns(params, s_ntt, c0, c1, use_pallas=use_pallas)
    return [extract_scores(params, d_rns[b], nd, nc)
            for b, (nd, nc) in enumerate(meta)]


def cosine_distances(scores: np.ndarray) -> np.ndarray:
    """Paper Definition 2 over decrypted inner products."""
    return 1.0 - scores


__all__ = [
    "RlweParams", "RlweSecretKey", "QueryCiphertext", "PackedCandidates",
    "ScoreCiphertexts", "ScoreCiphertextBatch", "CandidateCache",
    "CandidateCacheConfig", "ShardedCandidateCache",
    "build_sharded_candidate_cache", "shard_candidate_cache",
    "densify_candidate_cache",
    "params_key", "build_candidate_cache", "keygen", "encrypt_query",
    "decrypt_scores", "decrypt_scores_batch", "decrypt_rns",
    "extract_scores", "pack_candidates", "pack_candidates_batch",
    "encrypted_scores", "encrypted_scores_batch",
    "encrypted_scores_batch_stacked", "encrypted_scores_cached",
    "encrypted_scores_cached_batch", "cosine_distances",
]
