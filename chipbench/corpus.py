"""The benchmark's own data: corpus embeddings, documents and queries.

Everything is drawn from a `numpy.random.SeedSequence` child that the
caller derives from ``--seed``, so a seed gives the same data on any
machine.  Embeddings are unit float32 rows drawn in blocks (never through
float64: 10^6 x 768 would be 6 GB of host memory).

A configuration without a ``corpus`` block gets i.i.d. uniform unit rows
(`unit_rows`).  One with it gets a topic-clustered corpus (`topic_rows`):
``topics`` unit centres, each document its topic's centre plus isotropic
Gaussian noise of ``spread`` per coordinate, renormalised, stored grouped by
topic as contiguous row ranges (the order an index clustered at ingest
holds them in), with topic sizes Zipf(``size_zipf``) over the topics'
ranks (0: equal sizes)."""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from chipbench.loadgen import zipf_counts
from chipbench.spec import SpecError

BLOCK_ROWS = 1 << 16
CORPUS_KEYS = {"topics", "spread", "size_zipf"}


def unit_rows(rng: np.random.Generator, n: int, dim: int) -> np.ndarray:
    """(n, dim) float32 rows, each of unit norm, drawn block by block."""
    out = np.empty((n, dim), np.float32)
    for lo in range(0, n, BLOCK_ROWS):
        hi = min(n, lo + BLOCK_ROWS)
        x = rng.standard_normal((hi - lo, dim), dtype=np.float32)
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        out[lo:hi] = x
    return out


def topic_bounds(n: int, topics: int, size_zipf: float) -> np.ndarray:
    """First row of each topic and the end: (topics + 1,) int64.  Topic t
    holds rows ``[bounds[t], bounds[t + 1])``, Zipf(size_zipf) over the
    topic's rank t, rounded by largest remainder."""
    sizes = zipf_counts(n, topics, size_zipf)
    if sizes.min() < 1:
        raise SpecError(f"{topics} topics of Zipf({size_zipf}) sizes over "
                        f"{n} docs leave a topic empty")
    return np.concatenate([[0], np.cumsum(sizes)])


def topic_rows(rng: np.random.Generator, n: int, dim: int,
               block: dict) -> Tuple[np.ndarray, np.ndarray]:
    """The topic-clustered corpus of a configuration's ``corpus`` block:
    (n, dim) unit float32 rows grouped by topic, and `topic_bounds`.  The
    centres are drawn first, then the documents block by block."""
    unknown = set(block) - CORPUS_KEYS
    if unknown or "topics" not in block or "spread" not in block:
        raise SpecError(f"corpus block takes {sorted(CORPUS_KEYS)} "
                        f"(topics and spread required), got {sorted(block)}")
    topics, spread = int(block["topics"]), np.float32(block["spread"])
    if not 1 <= topics <= n or not spread > 0:
        raise SpecError(f"corpus block needs 1 <= topics <= {n} and "
                        f"spread > 0, got {block}")
    bounds = topic_bounds(n, topics, float(block.get("size_zipf", 0.0)))
    centres = unit_rows(rng, topics, dim)
    out = np.empty((n, dim), np.float32)
    for lo in range(0, n, BLOCK_ROWS):
        hi = min(n, lo + BLOCK_ROWS)
        topic = np.searchsorted(bounds, np.arange(lo, hi), side="right") - 1
        x = rng.standard_normal((hi - lo, dim), dtype=np.float32)
        x *= spread
        x += centres[topic]
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        out[lo:hi] = x
    return out, bounds


def embeddings(rng: np.random.Generator, cfg: dict
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """A configuration's corpus and its topic bounds (None without a
    ``corpus`` block)."""
    block = cfg.get("corpus")
    if block is None:
        return unit_rows(rng, cfg["n_docs"], cfg["dim"]), None
    return topic_rows(rng, cfg["n_docs"], cfg["dim"], block)


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
