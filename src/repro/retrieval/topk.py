"""Distributed exact top-k' search over a sharded FlatIndex.

Per-device: the fused Pallas score+select kernel reduces the local shard to
(B, k_local) candidates.  Cross-device: every shard all-gathers the others'
candidates and runs the same tiny top-k merge, so the result leaves the
shard_map replicated — on Auto and Explicit mesh axes alike.  Collective
bytes scale with devices * B * k (KB), never with N.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import numpy as np

from repro.kernels.scoretopk import ops as sops
from repro.retrieval.index import FlatIndex, IndexSlice


class SearchResult(NamedTuple):
    values: jax.Array    # (B, k) descending scores (inner products)
    indices: jax.Array   # (B, k) int32 global ids
    exact: jax.Array     # () bool


def make_sharded_topk(mesh, axes, n_rows: int, k: int, *,
                      tile: Optional[int] = None,
                      per_tile_k: Optional[int] = None, use_pallas=None):
    """Functional core: (queries, corpus) -> SearchResult, jit/lower-able.

    ``corpus`` must be row-sharded over ``axes``; rows must divide evenly.
    """
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    rows_local = n_rows // n_shards
    k_local = min(k, rows_local)
    k_eff = min(k, n_shards * k_local)

    def local_search(q, shard):
        # linearized shard position over the row axes
        pos = jnp.int32(0)
        for a in axes:
            pos = pos * mesh.shape[a] + jax.lax.axis_index(a)
        out = sops.topk_scores(q, shard, k_local, tile=tile,
                               per_tile_k=per_tile_k, use_pallas=use_pallas)
        gidx = out.indices + pos * rows_local
        # (n_shards, B, k_local) in shard order on every device, then the
        # merge: shard-major ties -> lower global id first, as one scan
        vals = jax.lax.all_gather(out.values, axes)
        ids = jax.lax.all_gather(gidx, axes)
        ok = jax.lax.all_gather(out.exact, axes)
        b = q.shape[0]
        flat_v = jnp.swapaxes(vals, 0, 1).reshape(b, n_shards * k_local)
        flat_i = jnp.swapaxes(ids, 0, 1).reshape(b, n_shards * k_local)
        mv, mpos = jax.lax.top_k(flat_v, k_eff)
        return mv, jnp.take_along_axis(flat_i, mpos, axis=1), jnp.all(ok)

    def search(queries, corpus):
        mv, mi, ok = jax.shard_map(
            local_search, mesh=mesh,
            in_specs=(P(), P(axes, None)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(queries, corpus)
        return SearchResult(mv, mi, ok)

    return search


def distributed_topk(index: FlatIndex, queries, k: int, *,
                     tile: Optional[int] = None,
                     per_tile_k: Optional[int] = None,
                     use_pallas=None) -> SearchResult:
    """Exact top-k of <query, corpus row> over the (possibly sharded) index."""
    n_rows = index.num_rows  # includes shard padding
    if index.mesh is None:
        out = sops.topk_scores(queries, index.embeddings, k, tile=tile,
                               per_tile_k=per_tile_k, use_pallas=use_pallas)
        return SearchResult(out.values, out.indices, out.exact)
    search = make_sharded_topk(index.mesh, index.row_axes, n_rows, k,
                               tile=tile, per_tile_k=per_tile_k,
                               use_pallas=use_pallas)
    return search(queries, index.embeddings)


def slice_topk(sl: IndexSlice, queries, k: int, *,
               tile: Optional[int] = None,
               per_tile_k: Optional[int] = None,
               use_pallas=None) -> SearchResult:
    """Exact top-k over one replica's row slice, in *global* ids.

    Runs the same fused score+select as the full-index path (same tile
    schedule, same stable tie-break toward lower row id), then offsets
    local ids by ``sl.start``.  Per-slice results merged by (score desc,
    global id asc) therefore reproduce the full-index top-k bit-for-bit —
    the invariant the scale-out router's differential harness pins.
    """
    k_local = min(k, sl.num_rows)
    out = sops.topk_scores(queries, sl.embeddings, k_local, tile=tile,
                           per_tile_k=per_tile_k, use_pallas=use_pallas)
    return SearchResult(out.values, out.indices + sl.start, out.exact)


def plan_nprobe(cluster_map, kprime: int, *, slack: float = 4.0) -> int:
    """Theorem-1 search range -> IVF probe bound.

    The planner guarantees the true top-k lie inside the k' nearest rows
    of the perturbed query; routing must therefore scan at least enough
    clusters to contain those k' rows.  Conservatively: the smallest n
    such that even the n *smallest* clusters hold ``slack * kprime``
    docs — so whichever clusters the router actually picks, the scanned
    candidate pool covers the planned search range with ``slack``x
    headroom.  Clamped to [1, num_clusters]."""
    if kprime < 1:
        raise ValueError(f"kprime must be >= 1, got {kprime}")
    sizes = np.sort(np.asarray(cluster_map.sizes, np.int64))
    need = min(int(sizes.sum()), int(np.ceil(slack * kprime)))
    cum = np.cumsum(sizes)
    n = int(np.searchsorted(cum, need)) + 1
    return max(1, min(n, int(sizes.size)))


def cluster_topk(view, queries, k: int, *, nprobe: Optional[int] = None,
                 tile: Optional[int] = None,
                 per_tile_k: Optional[int] = None,
                 use_pallas=None) -> SearchResult:
    """IVF first-stage routed top-k over a `CorpusView` (or any object
    with ``cluster_map`` + ``cluster_slice``).

    Each query routes to its ``nprobe`` nearest clusters (centroid score
    desc, cluster id asc); each routed cluster's contiguous slice runs the
    same fused per-slice scan as the replica router (`slice_topk`), and
    per-query results merge by (score desc, global id asc).  With
    ``nprobe=None`` (or >= the cluster count) every cluster is scanned and
    the result is bit-identical to the flat `distributed_topk` scan — the
    differential anchor; smaller ``nprobe`` trades recall outside the
    routed clusters for skipping their rows entirely (``exact`` is then
    False).  Use `plan_nprobe` to derive the probe count from the
    Theorem-1 plan's k'."""
    cm = view.cluster_map
    if cm is None:
        raise ValueError("cluster_topk needs an IVF-built corpus "
                         "(FlatIndex.build(ivf=...))")
    num_clusters = cm.num_clusters
    probe = num_clusters if nprobe is None else max(1, min(int(nprobe),
                                                           num_clusters))
    # routing and the per-cluster query selection stay on the host: a
    # device gather per cluster costs more than the cluster's scan
    queries = np.asarray(queries, np.float32)
    bsz = queries.shape[0]
    routed = cm.route(queries, probe)                        # (B, probe)
    if np.min(cm.sizes[routed].sum(axis=1)) < k:
        raise ValueError(
            f"nprobe={probe} routes fewer than k={k} rows; raise nprobe")
    vals = [[] for _ in range(bsz)]
    gids = [[] for _ in range(bsz)]
    exact = True
    for c in np.unique(routed):
        qsel = np.nonzero((routed == int(c)).any(axis=1))[0]
        out = slice_topk(view.cluster_slice(int(c)), queries[qsel], k,
                         tile=tile, per_tile_k=per_tile_k,
                         use_pallas=use_pallas)
        exact = exact and bool(out.exact)
        ov = np.asarray(out.values)
        oi = np.asarray(out.indices)
        for j, q in enumerate(qsel):
            vals[int(q)].append(ov[j])
            gids[int(q)].append(oi[j])
    mv = np.empty((bsz, k), np.float32)
    mi = np.empty((bsz, k), np.int32)
    for b in range(bsz):
        v = np.concatenate(vals[b])
        g = np.concatenate(gids[b])
        order = np.lexsort((g, -v))[:k]     # score desc, global id asc
        mv[b] = v[order]
        mi[b] = g[order]
    return SearchResult(jnp.asarray(mv), jnp.asarray(mi),
                        jnp.asarray(exact and probe == num_clusters))


def distances_from_scores(values):
    """Cosine distance (paper Definition 2) from inner-product scores."""
    return 1.0 - values


__all__ = ["SearchResult", "make_sharded_topk", "distributed_topk",
           "slice_topk", "cluster_topk", "plan_nprobe",
           "distances_from_scores"]
