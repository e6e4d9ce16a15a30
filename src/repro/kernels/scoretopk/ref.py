"""Pure-jnp oracle for the fused score+select kernel.

Scores are inner products (cosine similarity for unit-norm rows); the
RemoteRAG cosine *distance* is 1 - score.  Ties break toward the lower index
(XLA top_k semantics), matching the kernel's tile-major merge order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# Every score is one entry of a (QUERY_BLOCK, n) x (ROW_BLOCK, n) dot.
QUERY_BLOCK, ROW_BLOCK = 8, 512


def _dot(q, e):
    return jax.lax.dot_general(
        q, e, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@jax.jit
def score_ref(queries, corpus):
    """(B, n) x (N, n) -> (B, N) inner-product scores in f32.

    Scored in full-f32 dots of one fixed shape, (QUERY_BLOCK, n) x
    (ROW_BLOCK, n): XLA:CPU's f32 dot rounds a (query, row) pair
    differently with the operands' shapes, not with the pair's place in
    them, so every score is a pure function of its pair — the same in a
    batch of 8 and a solo retry, in a full scan and in the per-slice scans
    the replica router merges, where exact ties must stay ties.  Queries
    are padded to whole blocks; the last row block is the corpus's last
    ROW_BLOCK rows (overlapping its predecessor), so the corpus is never
    copied, and memory stays O(B * N)."""
    q = queries.astype(jnp.float32)
    e = corpus.astype(jnp.float32)
    b, n = q.shape
    n_rows = e.shape[0]
    qb = -(-b // QUERY_BLOCK)
    q = jnp.pad(q, ((0, qb * QUERY_BLOCK - b), (0, 0)))
    q = q.reshape(qb, QUERY_BLOCK, n)
    if n_rows < ROW_BLOCK:
        e = jnp.pad(e, ((0, ROW_BLOCK - n_rows), (0, 0)))
    total = e.shape[0]
    nt = -(-total // ROW_BLOCK)
    starts = jnp.minimum(jnp.arange(nt) * ROW_BLOCK, total - ROW_BLOCK)

    def row_block(start):
        blk = jax.lax.dynamic_slice_in_dim(e, start, ROW_BLOCK)
        return jax.lax.map(lambda qq: _dot(qq, blk), q)

    s = jax.lax.map(row_block, starts)   # (nt, qb, QUERY_BLOCK, ROW_BLOCK)
    s = s.transpose(1, 2, 0, 3).reshape(qb * QUERY_BLOCK, nt, ROW_BLOCK)
    overlap = nt * ROW_BLOCK - total          # rows the last block repeats
    s = jnp.concatenate([s[:, :-1].reshape(qb * QUERY_BLOCK,
                                           (nt - 1) * ROW_BLOCK),
                         s[:, -1, overlap:]], axis=1)
    return s[:b, :n_rows]


def topk_ref(queries, corpus, k: int):
    """Exact top-k scores+indices per query: (B, k) vals, (B, k) int32 idx."""
    scores = score_ref(queries, corpus)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("kk", "tile"))
def tile_topk_ref(queries, corpus, kk: int, tile: int):
    """Per-tile top-kk (the kernel's actual contract).

    Returns (num_tiles, B, kk) vals and global idx; tiles shorter than
    ``tile`` are padded with -inf / index N.
    """
    b = queries.shape[0]
    n_rows = corpus.shape[0]
    num_tiles = -(-n_rows // tile)
    pad = num_tiles * tile - n_rows
    scores = score_ref(queries, corpus)  # (B, N)
    scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    tiles = scores.reshape(b, num_tiles, tile).transpose(1, 0, 2)
    vals, idx = jax.lax.top_k(tiles, kk)  # (num_tiles, B, kk)
    gidx = idx + (jnp.arange(num_tiles, dtype=jnp.int32) * tile)[:, None, None]
    return vals, gidx.astype(jnp.int32)


def merge_tiles_ref(vals, gidx, k: int):
    """Merge per-tile candidates into global top-k (tile-major tie order)."""
    num_tiles, b, kk = vals.shape
    flat_v = vals.transpose(1, 0, 2).reshape(b, num_tiles * kk)
    flat_i = gidx.transpose(1, 0, 2).reshape(b, num_tiles * kk)
    mv, mpos = jax.lax.top_k(flat_v, k)
    mi = jnp.take_along_axis(flat_i, mpos, axis=1)
    return mv, mi


__all__ = ["score_ref", "topk_ref", "tile_topk_ref", "merge_tiles_ref"]
