"""Public API: exact top-k over a corpus with the fused kernel + certificate."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, resolve_use_pallas
from repro.kernels.scoretopk import ref as _ref
from repro.kernels.scoretopk import scoretopk as _kern


class TopK(NamedTuple):
    values: jax.Array   # (B, k) scores, descending
    indices: jax.Array  # (B, k) int32 global row ids
    exact: jax.Array    # () bool — certificate that the result is exact


def topk_scores(queries, corpus, k: int, *, tile: int | None = None,
                per_tile_k: int | None = None, use_pallas=None) -> TopK:
    """Exact top-k inner-product search.

    ``tile`` (corpus rows per kernel step) defaults to
    `scoretopk.corpus_tile` of the embedding width, capped at the corpus
    size.  ``per_tile_k`` < k trades selection work for a (checked)
    exactness certificate: the merged result is exact iff no tile
    contributed all of its per-tile candidates.  Default per_tile_k =
    min(k, tile) which is always exact.
    """
    n_rows, n = corpus.shape
    tile = min(_kern.corpus_tile(n) if tile is None else tile, n_rows)
    k = min(k, n_rows)
    kk = min(per_tile_k or k, k, tile)
    pallas = resolve_use_pallas(use_pallas)
    return _topk(queries, corpus, k=k, kk=kk, tile=tile, pallas=pallas,
                 interpret=pallas and interpret_mode())


@functools.partial(jax.jit, static_argnames=("k", "kk", "tile", "pallas",
                                             "interpret"))
def _topk(queries, corpus, *, k, kk, tile, pallas, interpret) -> TopK:
    """Scan, merge and certificate as one program (one dispatch)."""
    if pallas:
        vals, gidx = _kern.score_topk_pallas(
            queries, corpus, kk=kk, tile=tile, interpret=interpret)
    else:
        vals, gidx = _ref.tile_topk_ref(queries, corpus, kk, tile)
    mv, mi = _ref.merge_tiles_ref(vals, gidx, k)
    exact = _certificate(gidx, mi, kk) if kk < k else jnp.asarray(True)
    return TopK(mv, mi, exact)


def _certificate(tile_idx, merged_idx, kk: int):
    """True iff every tile contributed < kk entries to the merged top-k."""
    num_tiles = tile_idx.shape[0]
    # tile of each merged index = merged_idx // tile-size; recover from the
    # per-tile candidate layout instead: membership count per tile.
    b = merged_idx.shape[0]
    cand = tile_idx.transpose(1, 0, 2).reshape(b, num_tiles, kk)
    member = (cand[:, :, :, None] == merged_idx[:, None, None, :]).any(-1)
    per_tile = member.sum(-1)  # (B, num_tiles)
    return jnp.all(per_tile < kk)


def exact_fallback(queries, corpus, k: int) -> TopK:
    vals, idx = _ref.topk_ref(queries, corpus, k)
    return TopK(vals, idx, jnp.asarray(True))


__all__ = ["TopK", "topk_scores", "exact_fallback"]
