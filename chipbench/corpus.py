"""The benchmark's own data: corpus embeddings, documents and queries.

Everything is drawn from a `numpy.random.SeedSequence` child that the
caller derives from ``--seed``, so a seed gives the same data on any
machine.  Embeddings are unit float32 rows drawn in blocks (never through
float64: 10^6 x 768 would be 6 GB of host memory)."""

from __future__ import annotations

from typing import List

import numpy as np

BLOCK_ROWS = 1 << 16


def unit_rows(rng: np.random.Generator, n: int, dim: int) -> np.ndarray:
    """(n, dim) float32 rows, each of unit norm, drawn block by block."""
    out = np.empty((n, dim), np.float32)
    for lo in range(0, n, BLOCK_ROWS):
        hi = min(n, lo + BLOCK_ROWS)
        x = rng.standard_normal((hi - lo, dim), dtype=np.float32)
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        out[lo:hi] = x
    return out


def payloads(rng: np.random.Generator, n: int,
             mean_bytes: int) -> List[bytes]:
    """``n`` lower-case ASCII documents, Poisson(mean_bytes) long (at least
    16 bytes), cut from one buffer so 10^6 of them take a second."""
    lens = np.maximum(rng.poisson(mean_bytes, size=n), 16)
    buf = rng.integers(97, 123, size=int(lens.sum()), dtype=np.uint8)
    buf = buf.tobytes()
    ends = np.cumsum(lens)
    starts = ends - lens
    return [buf[s:e] for s, e in zip(starts.tolist(), ends.tolist())]


def queries_near(rng: np.random.Generator, emb: np.ndarray,
                 rows: np.ndarray, jitter: float) -> np.ndarray:
    """One unit float32 query near each corpus row in ``rows``: the row
    plus isotropic Gaussian noise of scale ``jitter``, renormalised."""
    q = emb[rows] + (rng.standard_normal((len(rows), emb.shape[1]),
                                         dtype=np.float32)
                     * np.float32(jitter))
    return (q / np.linalg.norm(q, axis=1, keepdims=True)).astype(np.float32)


def seeds(seed: int) -> dict:
    """Independent streams for each part of a run, from ``--seed``."""
    names = ("corpus", "docs", "traffic", "queries", "keys", "tenants",
             "warmup", "sample")
    children = np.random.SeedSequence(int(seed)).spawn(len(names))
    return dict(zip(names, children))


def tenant_seed(seed_seq: np.random.SeedSequence, tenant: int) -> int:
    """The key-generation seed of tenant ``tenant``."""
    child = np.random.SeedSequence(seed_seq.entropy,
                                   spawn_key=seed_seq.spawn_key + (tenant,))
    return int(child.generate_state(1, np.uint64)[0])
