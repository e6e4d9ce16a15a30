"""Serving driver: the private RAG service end to end.

Builds a synthetic corpus + FlatIndex, spins up the micro-batching
`repro.serve` engine with a pool of tenant sessions, and serves a stream of
queries through the full protocol (Module 1 DistanceDP + range limitation,
Module 2a encrypted re-rank, Module 2b/2c retrieval), printing latency and
wire-size stats per request plus the per-tenant engine metrics.

`python -m repro.launch.serve --n-docs 20000 --requests 8 --backend rlwe`
`... --no-batch` runs the sequential one-query-at-a-time comparison path.
`... --replicas N` serves through the scale-out `ReplicaRouter` (N engine
replicas over contiguous corpus slices, scatter-gather top-k'; results
stay bit-identical to a single engine — docs/scale_out.md) and prints the
router summary instead of the single-engine one.
`... --trace-out trace.json` enables stage-level span tracing (repro.obs)
and writes a Chrome-trace timeline loadable at https://ui.perfetto.dev;
the summary then carries per-stage latency histograms.
`... --seed S` picks the synthetic corpus and query stream.

The process exits 1 when any request ends in an error (a failed lane after
its quarantine retry); typed admission drops (rejections, sheds) are
reported but are not errors.  `main()` is a thin shell over `parse_args`,
`build_index` and `serve`, which `chip_smoke.py` drives directly.  JAX's
persistent compilation cache is enabled by `main()` (never at import):
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the repo
root (`enable_compile_cache`).

Admission control (off unless one of these is set): `--tenant-rate R`
installs per-tenant token buckets, `--max-queue N` bounds the global
queue with priority displacement, `--deadline-ms MS` applies a default
SLO budget with deadline-aware shedding, `--priority CLASS` picks the
default class.  Typed rejections (`RateLimited`, `QueueFull`, ...) and
shed results are printed per request — the submit loop never dies on
backpressure.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

import jax

from repro.crypto import backend as crypto_backend
from repro.data import synth
from repro.retrieval.index import FlatIndex, IvfConfig
from repro.retrieval.topk import plan_nprobe
from repro.serve import (AdmissionConfig, AdmissionError, EngineConfig,
                         RateLimited, ReplicaRouter, RouterConfig,
                         ServeEngine, ServeResult)
from repro.serve.admission import PRIORITIES
from repro.serve.session import PlanCache, SessionManager

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  ``$JAX_COMPILATION_CACHE_DIR`` wins when set
    (JAX reads it itself; no other path is set here); otherwise the cache
    lives at the fixed ``.jax_cache/`` of the repo root — a fixed path,
    because the path is part of what a later run must find again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # every compile is worth keeping: the served kernels compile in about
    # a second each, under JAX's default one-second threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=384)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--radius", type=float, default=0.05)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic corpus and query stream")
    ap.add_argument("--backend", choices=crypto_backend.available(),
                    default="rlwe")
    ap.add_argument("--corpus", choices=("uniform", "clustered"),
                    default="uniform")
    ap.add_argument("--ivf-clusters", type=int, default=None, metavar="C",
                    help="build the index with C-cluster IVF first-stage "
                         "routing (k-means at build, cluster-aligned row "
                         "layout; docs/corpus.md); replica slices then "
                         "land on cluster boundaries")
    ap.add_argument("--nprobe", default=None, metavar="N|auto",
                    help="clusters scanned per query (needs "
                         "--ivf-clusters): an integer, or 'auto' for the "
                         "planner-derived Theorem-1 bound "
                         "(plan_nprobe on the session plan's k'); N >= C "
                         "is bit-identical to the flat scan")
    ap.add_argument("--ingest", type=int, default=None, metavar="D",
                    help="after the first wave, ingest D new docs (tail-"
                         "shard append, epoch advance), refresh/replan, "
                         "and serve the stream again at the new epoch")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=20.0)
    ap.add_argument("--no-batch", action="store_true",
                    help="sequential comparison path (one query per step)")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="N > 1 serves through a ReplicaRouter: N engine "
                         "replicas over contiguous corpus slices with "
                         "scatter-gather top-k' (bit-identical to N=1); "
                         "prints the router summary (docs/scale_out.md)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable stage tracing and write a Perfetto-"
                         "loadable Chrome-trace JSON timeline to PATH")
    ap.add_argument("--tenant-rate", type=float, default=None, metavar="R",
                    help="per-tenant token-bucket rate limit in "
                         "requests/s (enables the admission tier; "
                         "rejections surface as rate_limited drops)")
    ap.add_argument("--max-queue", type=int, default=None, metavar="N",
                    help="bound the global request queue at N; a full "
                         "queue displaces lower-priority work or rejects "
                         "the submit (queue_full drops, counted)")
    ap.add_argument("--deadline-ms", type=float, default=None, metavar="MS",
                    help="default per-request SLO budget; requests whose "
                         "remaining budget cannot cover the observed "
                         "dispatch latency are shed before any crypto")
    ap.add_argument("--priority", choices=PRIORITIES, default=None,
                    help="default admission priority class (interactive "
                         "degrades last under overload)")
    return ap


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = _parser()
    args = ap.parse_args(argv)
    if args.tenants < 1 or args.requests < 1:
        ap.error("--tenants and --requests must be >= 1")
    if args.ivf_clusters is not None and args.ivf_clusters < 1:
        ap.error("--ivf-clusters must be >= 1")
    if args.ivf_clusters is None and args.nprobe is not None:
        ap.error("--nprobe needs --ivf-clusters")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1 and args.no_batch:
        ap.error("--replicas > 1 is the batched path; drop --no-batch")
    return args


def _corpus_gen(args):
    return (synth.uniform_corpus if args.corpus == "uniform"
            else synth.clustered_corpus)


def build_index(args: argparse.Namespace) -> FlatIndex:
    """The synthetic corpus of ``args`` (from ``--seed``) as a FlatIndex."""
    rng = np.random.default_rng(args.seed)
    emb = _corpus_gen(args)(rng, args.n_docs, args.dim)
    docs = synth.passages(rng, args.n_docs, avg_bytes=256)
    ivf = (None if args.ivf_clusters is None
           else IvfConfig(num_clusters=args.ivf_clusters))
    return FlatIndex.build(emb, documents=docs, ivf=ivf)


@dataclasses.dataclass
class ServeReport:
    """What one `serve` call returns: the session plan, every result in
    request order (both waves when ``--ingest`` ran) with the query it
    answered, and the engine or router summary."""
    plan: object
    results: List[ServeResult]
    queries: dict                 # request id -> query embedding
    summary: dict
    rejected: int = 0

    @property
    def errors(self) -> int:
        """Requests that ended in an error (not admission drops)."""
        return sum(1 for r in self.results
                   if not r.ok and r.shed_reason is None)


def serve(args: argparse.Namespace, index: Optional[FlatIndex] = None, *,
          use_pallas: Optional[bool] = None,
          deterministic_seeds: bool = False,
          emit: Callable[[str], None] = print) -> ServeReport:
    """Serve ``args.requests`` queries through the engine (or router) and
    report each as one JSON line through ``emit``.  ``index`` defaults to
    `build_index(args)`; ``use_pallas`` overrides the engine's kernel
    choice (None: Pallas on a TPU); ``deterministic_seeds`` derives tenant
    keys from tenant names, so two calls replay identical bytes (parity
    runs only — such keys are public)."""
    if index is None:
        index = build_index(args)
    # IVF builds permute rows into cluster-contiguous order, so result
    # ids live in the index's row space — score recall against that.
    emb = np.asarray(index.embeddings)
    rng = np.random.default_rng((args.seed, 1))

    nprobe = None
    if args.nprobe is not None:
        if args.nprobe == "auto":
            # the Theorem-1 probe bound for this session shape: enough
            # clusters that the planned k'-row search range is covered
            plan = PlanCache().get(n=args.dim, N=args.n_docs, k=args.k,
                                   radius=args.radius)
            nprobe = plan_nprobe(index.cluster_map, plan.kprime)
        else:
            nprobe = int(args.nprobe)
    if index.cluster_map is not None:
        emit(json.dumps({"ivf": {
            "clusters": index.cluster_map.num_clusters,
            "nprobe": nprobe if nprobe is not None else "all"}}))

    admission = None
    if (args.tenant_rate is not None or args.max_queue is not None
            or args.deadline_ms is not None or args.priority is not None):
        admission = AdmissionConfig(
            tenant_rate=args.tenant_rate,
            max_queue=args.max_queue,
            default_deadline_s=(None if args.deadline_ms is None
                                else args.deadline_ms / 1e3),
            default_priority=args.priority or "interactive")

    ecfg = EngineConfig(
        max_batch=1 if args.no_batch else args.max_batch,
        max_wait_s=args.max_wait_ms / 1e3,
        sequential=args.no_batch,
        use_pallas=use_pallas,
        trace=args.trace_out is not None,
        admission=admission,
        nprobe=nprobe)
    sessions = SessionManager(deterministic_seeds=deterministic_seeds)
    # context manager: close() drains leftovers and stops the sharded
    # cache's background admitter thread on exit (no thread leak across
    # engine lifetimes); the router additionally stops its per-replica
    # worker pools
    service = (ReplicaRouter(index, config=RouterConfig(
                   num_replicas=args.replicas, engine=ecfg),
                   sessions=sessions)
               if args.replicas > 1 else
               ServeEngine(index, config=ecfg, sessions=sessions))
    all_results: List[ServeResult] = []
    all_queries: dict = {}
    with service as engine:
        for t in range(args.tenants):
            sess = engine.open_session(f"tenant-{t}", n=args.dim,
                                       N=args.n_docs, k=args.k,
                                       radius=args.radius,
                                       backend=args.backend)
        plan = sess.plan
        emit(json.dumps({"plan": {
            "eps": plan.eps, "kprime": plan.kprime, "path": plan.path,
            "radius": plan.radius,
            "plan_cache": {"hits": engine.sessions.plan_cache.hits,
                           "misses": engine.sessions.plan_cache.misses}}}))

        queries = synth.queries_near_corpus(rng, emb, args.requests)
        t0 = time.monotonic()
        rejected = 0
        rid_to_query = {}
        for i, q in enumerate(queries):
            tenant = f"tenant-{i % args.tenants}"
            # typed backpressure: a rejected submit is reported and the
            # loop continues — the client never dies on overload
            try:
                rid = engine.submit(tenant, q, key=jax.random.PRNGKey(i))
            except AdmissionError as e:
                rejected += 1
                rec = {"request": None, "tenant": tenant,
                       "rejected": type(e).__name__}
                if isinstance(e, RateLimited):
                    rec["retry_after_s"] = round(e.retry_after_s, 3)
                emit(json.dumps(rec))
                continue
            rid_to_query[rid] = q
        results = engine.drain()
        wall = time.monotonic() - t0
        all_results.extend(results)
        all_queries.update(rid_to_query)

        for res in results:
            if res.shed_reason is not None:  # admission-tier shed, no crypto
                emit(json.dumps({
                    "request": res.request_id, "tenant": res.tenant,
                    "latency_s": round(res.latency_s, 3),
                    "shed": res.shed_reason}))
                continue
            if not res.ok:  # lane failed after its quarantine retry
                emit(json.dumps({
                    "request": res.request_id, "tenant": res.tenant,
                    "latency_s": round(res.latency_s, 3),
                    "quarantined": res.quarantined,
                    "error": res.error}))
                continue
            q = rid_to_query[res.request_id]
            plain = np.argsort(-(emb @ q), kind="stable")[: args.k]
            recall = (len(set(res.ids.tolist()) & set(plain.tolist()))
                      / args.k)
            emit(json.dumps({
                "request": res.request_id, "tenant": res.tenant,
                "latency_s": round(res.latency_s, 3),
                "batch_size": res.batch_size, "recall": recall,
                "wire_bytes": res.transcript.total_bytes,
                "path": res.transcript.path}))
        if args.replicas > 1:
            out = engine.summary()
            out["router"]["qps"] = round(len(results) / wall, 3)
        else:
            summary = engine.metrics.summary()
            summary["aggregate"]["qps"] = round(len(results) / wall, 3)
            occupancy = engine.metrics.occupancy(engine.config.max_batch)
            out = {"summary": summary["aggregate"],
                   "num_batches": summary["num_batches"],
                   "occupancy": None if occupancy is None
                   else round(occupancy, 3)}
            if "failures" in summary:
                out["failures"] = summary["failures"]
            if "admission" in summary:
                out["admission"] = dict(summary["admission"],
                                        rejected_submits=rejected)
            if "trace" in summary:
                out["stages"] = summary["trace"]["stages"]
        emit(json.dumps(out))
        if args.ingest is not None and args.ingest >= 1:
            # streaming ingestion: tail-shard append + epoch advance while
            # the service stays up, then the same stream at the new epoch
            rng2 = np.random.default_rng((args.seed, 2))
            new_emb = _corpus_gen(args)(rng2, args.ingest, args.dim)
            new_docs = synth.passages(rng2, args.ingest, avg_bytes=256)
            t0 = time.monotonic()
            view = index.ingest(new_emb, documents=new_docs)
            spans = (engine.replan() if args.replicas > 1
                     else (engine.refresh_corpus() and None))
            ingest_ms = (time.monotonic() - t0) * 1e3
            emit(json.dumps({"ingest": {
                "docs": args.ingest, "epoch": view.epoch,
                "num_rows": index.num_rows,
                "ingest_ms": round(ingest_ms, 1),
                "replanned_slices": spans}}))
            grown = np.asarray(index.embeddings)
            for t in range(args.tenants):   # re-plan sessions for the
                engine.open_session(        # grown corpus + new epoch
                    f"tenant-{t}@e{view.epoch}", n=args.dim,
                    N=index.num_rows, k=args.k, radius=args.radius,
                    backend=args.backend)
            rid_to_query = {}
            for i, q in enumerate(queries):
                rid = engine.submit(
                    f"tenant-{i % args.tenants}@e{view.epoch}", q,
                    key=jax.random.PRNGKey(10_000 + i))
                rid_to_query[rid] = q
            wave = engine.drain()
            all_results.extend(wave)
            all_queries.update(rid_to_query)
            for res in wave:
                if not res.ok:
                    emit(json.dumps({
                        "request": res.request_id, "tenant": res.tenant,
                        "epoch": view.epoch, "error": res.error}))
                    continue
                q = rid_to_query[res.request_id]
                plain = np.argsort(-(grown @ q), kind="stable")[: args.k]
                recall = (len(set(res.ids.tolist()) & set(plain.tolist()))
                          / args.k)
                emit(json.dumps({
                    "request": res.request_id, "tenant": res.tenant,
                    "epoch": view.epoch,
                    "latency_s": round(res.latency_s, 3),
                    "recall": recall,
                    "wire_bytes": res.transcript.total_bytes}))
        if args.trace_out is not None:
            n_events = engine.write_trace(args.trace_out)
            emit(json.dumps({"trace_out": args.trace_out,
                             "trace_events": n_events,
                             "view": "https://ui.perfetto.dev"}))
    return ServeReport(plan=plan, results=all_results, queries=all_queries,
                       summary=out, rejected=rejected)


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    report = serve(args)
    if report.errors:
        print(f"{report.errors} request(s) ended in an error",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
