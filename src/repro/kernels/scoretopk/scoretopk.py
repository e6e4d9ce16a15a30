"""Pallas TPU kernel: fused corpus scoring + per-tile top-k selection.

RemoteRAG Module 1 scores the perturbed query against the full corpus shard
and keeps the top-k' — a streaming, memory-bound matmul whose output (all N
scores) is pure waste if materialized.  This kernel fuses:

  HBM corpus tile (T, n) -> VMEM -> MXU matmul vs resident queries (B, n)
  -> per-tile top-kk selection (VPU iterative max-extract, no sort)

so only (num_tiles, B, kk) candidates ever reach HBM — an N/kk-fold output
reduction.  The tiny cross-tile merge happens outside (jnp top_k over
num_tiles*kk items); with kk == k' the union provably contains the global
top-k', and for kk < k' the caller checks an exactness certificate (no tile
contributed its full kk) and falls back to the exact path if violated.

Selection is kk iterations of (max, first-argmax, mask) over the tile's
scores: sort-free, fully vectorized over the batch.  The first argmax is the
least column holding the row max (what `jnp.argmax` returns), and each
iteration writes its column of the (B, kk) results with a select against a
column iota — no dynamic-index stores, which the TPU lowering lacks.

The corpus tile is derived from the embedding width (`corpus_tile`) so its
double-buffered VMEM copy fits the kernel's scoped VMEM at any width; the
last tile may overhang the corpus, its rows masked to -inf in-kernel (the
corpus is never padded or copied).  Scores are full-f32 MXU dots
(Precision.HIGHEST), as in the XLA oracle (`ref.score_ref`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM for one corpus tile; the pipeline double-buffers it, which keeps
# 2 * TILE_BYTES well inside the 16 MiB scoped VMEM of a v5e core.
TILE_BYTES = 4 << 20
MAX_TILE = 2048


def corpus_tile(n: int) -> int:
    """Rows per corpus tile at embedding width ``n``: the largest power of
    two <= MAX_TILE (and >= 8) whose f32 tile fits TILE_BYTES."""
    tile = MAX_TILE
    while tile > 8 and tile * n * 4 > TILE_BYTES:
        tile //= 2
    return tile


def _kernel(q_ref, e_ref, vals_ref, idx_ref, *, kk: int, tile: int, n_rows: int):
    i = pl.program_id(0)
    q = q_ref[...]            # (B, n)
    e = e_ref[...]            # (T, n)
    b = q.shape[0]
    scores = jax.lax.dot_general(
        q, e, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                       # (B, T)

    # mask rows beyond the real corpus (the last tile's overhang) to -inf
    col = jax.lax.broadcasted_iota(jnp.int32, (b, tile), 1)
    scores = jnp.where(i * tile + col < n_rows, scores, -jnp.inf)
    slot = jax.lax.broadcasted_iota(jnp.int32, (b, kk), 1)

    def body(j, carry):
        s, vacc, iacc = carry
        m = jnp.max(s, axis=1, keepdims=True)                       # (B, 1)
        am = jnp.min(jnp.where(s == m, col, tile), axis=1, keepdims=True)
        vacc = jnp.where(slot == j, m, vacc)
        iacc = jnp.where(slot == j, i * tile + am, iacc)
        s = jnp.where(col == am, -jnp.inf, s)
        return s, vacc, iacc

    vacc = jnp.full((b, kk), -jnp.inf, jnp.float32)
    iacc = jnp.full((b, kk), n_rows, jnp.int32)
    _, vacc, iacc = jax.lax.fori_loop(0, kk, body, (scores, vacc, iacc))
    vals_ref[0] = vacc
    idx_ref[0] = iacc


@functools.partial(jax.jit, static_argnames=("kk", "tile", "interpret"))
def score_topk_pallas(queries, corpus, *, kk: int, tile: int | None = None,
                      interpret: bool = True):
    """Fused scoring + per-tile top-kk.

    queries: (B, n) f32/bf16; corpus: (N, n).  ``tile`` defaults to
    `corpus_tile(n)`.  Returns vals (num_tiles, B, kk) f32 and global idx
    (num_tiles, B, kk) int32 (padded entries have val=-inf, idx=N).
    """
    b, n = queries.shape
    n_rows = corpus.shape[0]
    tile = corpus_tile(n) if tile is None else tile
    num_tiles = -(-n_rows // tile)
    kern = functools.partial(_kernel, kk=kk, tile=tile, n_rows=n_rows)
    return pl.pallas_call(
        kern,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((b, n), lambda i: (0, 0)),
            pl.BlockSpec((tile, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, b, kk), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b, kk), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles, b, kk), jnp.float32),
            jax.ShapeDtypeStruct((num_tiles, b, kk), jnp.int32),
        ],
        interpret=interpret,
        name="score_topk",
    )(queries.astype(jnp.float32), corpus.astype(jnp.float32))


__all__ = ["score_topk_pallas", "corpus_tile", "TILE_BYTES", "MAX_TILE"]
