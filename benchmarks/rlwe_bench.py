"""Encrypted re-rank hot path: cold per-request packing vs the NTT-domain
candidate cache, XLA fallback vs fused Pallas kernel, batch 1 / 8 — plus
the corpus-scale section: the dense device-resident cache vs the sharded
HBM-resident cache at 10^4 documents (10^5 under REPRO_BENCH_FULL=1), in
both access regimes — streaming on-demand gather under uniform-random ids
(the gated comparison; pinning is pure churn without locality) and
device-side gather from explicitly pinned hot shards under skewed ids (the
repeat-tenant case) — recording scoring latency, gather latency, and the
device memory footprint of each layout.

A final section runs *both* regimes against one default-policy config
(async, frequency-aware admission: 2nd-touch within a decayed window,
background H2D copy off the request path) — the configuration the serving
engine ships with — so the synchronous-admission churn regression stays
measurable.

Beyond the usual CSV rows this writes machine-readable ``BENCH_rlwe.json``
(path override: BENCH_RLWE_JSON) so the perf trajectory is trackable across
PRs; ``scripts/check_bench_regression.py`` gates CI on cached > cold, on
sharded batch-8 scoring staying within 1.3x of dense at a >= 4x smaller
peak cache footprint, and on the single default config staying within 1.2x
(skewed ids) / 1.3x (uniform ids) of dense at batch 8.  A stage-breakdown
section (repro.obs tracing over a served stream) records where request
time goes per pipeline stage; its stage-duration coverage of the dispatch
wall is gated too.  A ``paillier_batch`` section times the vectorized
RNS-limb Paillier batch path against the per-lane object path at batch
1 / 8; the batch-8 speedup (>= 3x), bit-exact decryption, and zero
silent object fallbacks are gated.

Two corpus-lifecycle sections close the file: ``ivf_routing`` times the
clustered first-stage scan (`repro.retrieval.topk.cluster_topk`) against
the flat scan at 10^4 docs — gated on >= 2x speedup, recall@k' == 1.0 at
the planner-derived ``nprobe``, and ``nprobe=all`` bit-identity with the
flat scan; ``ingestion`` drains a live serving stream across a tail-shard
ingest — gated on zero lost and zero bit-drifted requests while the cache
epoch advances.
"""

from __future__ import annotations

import json
import os

import numpy as np

import jax

from benchmarks.common import FULL, emit, timeit
from repro.crypto import rlwe

OUT_PATH = os.environ.get("BENCH_RLWE_JSON", "BENCH_rlwe.json")


def _unit(rng, *shape):
    x = rng.normal(size=shape)
    return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(np.float32)


def _serve_fault_section(params, rng) -> dict:
    """Fault injection on the serving engine's batched dispatch path:
    1-in-16 lanes persistently poisoned at batch 8.  Lane-level fault
    isolation must quarantine exactly the poisoned lane (one error result)
    while its 7 batchmates complete from their already-computed state —
    zero healthy-lane re-encryptions, batch occupancy within 0.9x of the
    fault-free run.  Both are CI-gated by
    ``scripts/check_bench_regression.py``."""
    import time

    from repro.retrieval.index import FlatIndex
    from repro.serve import EngineConfig, ServeEngine
    from repro.serve.session import SessionManager

    dim, num_docs, n_req, max_batch = 64, 2048, 16, 8
    emb = _unit(rng, num_docs, dim)
    index = FlatIndex.build(
        emb, documents=[f"doc-{i}".encode() for i in range(num_docs)])
    queries = _unit(rng, n_req, dim)

    def run_stream(poison_ids=None):
        # deterministic seeds + fixed per-request keys: both passes replay
        # identical streams, so the fault-free pass's result ids identify
        # the poisoned lane's fetches in the faulty pass
        eng = ServeEngine(
            index,
            config=EngineConfig(max_batch=max_batch, max_wait_s=30.0),
            sessions=SessionManager(rlwe_params=params,
                                    deterministic_seeds=True))
        for t in range(4):
            eng.open_session(f"bench-{t}", n=dim, N=num_docs, k=4,
                             radius=0.05, backend="rlwe")
        if poison_ids is not None:
            real = type(eng.cloud).handle_fetch

            def poisoned(cand_ids, msg):
                ids = [int(cand_ids[p]) for p in msg.positions]
                if ids == poison_ids:       # that lane and its solo retry
                    raise RuntimeError("bench-poisoned lane")
                return real(eng.cloud, cand_ids, msg)

            eng.cloud.handle_fetch = poisoned
        for i in range(n_req):
            eng.submit(f"bench-{i % 4}", queries[i],
                       key=jax.random.PRNGKey(i))
        t0 = time.perf_counter()
        out = eng.drain()
        wall_us = (time.perf_counter() - t0) * 1e6
        eng.close()
        return out, eng.metrics, wall_us

    clean, m_clean, clean_us = run_stream()
    assert all(r.ok for r in clean), "fault-free serve pass must succeed"
    faulty, m_fault, fault_us = run_stream(clean[0].ids.tolist())
    errors = [r for r in faulty if not r.ok]
    assert len(errors) == 1 and errors[0].request_id == 0, \
        "exactly the poisoned lane must error"
    for rs, rb in zip(clean[1:], faulty[1:]):
        assert rs.ids.tolist() == rb.ids.tolist(), \
            "healthy lanes must be unaffected by the poisoned lane"
    occ_clean = m_clean.occupancy(max_batch)
    occ_fault = m_fault.occupancy(max_batch)
    section = {
        "num_docs": num_docs,
        "requests": n_req,
        "max_batch": max_batch,
        "poisoned_lanes": 1,
        "wall_fault_free_us": clean_us,
        "wall_faulty_us": fault_us,
        "occupancy_fault_free": occ_clean,
        "occupancy_faulty": occ_fault,
        "occupancy_ratio": occ_fault / occ_clean,
        "healthy_lane_reencryptions": m_fault.healthy_reencryptions,
        "lane_encryptions": m_fault.lane_encryptions,
        "quarantined_lanes": m_fault.quarantined_lanes,
        "retried_requests": m_fault.retried_requests,
        "error_results": m_fault.error_results,
        "num_batches": m_fault.num_batches,
    }
    emit("rlwe/serve_fault_occupancy_b8", fault_us,
         f"{section['occupancy_ratio']:.2f}x_vs_fault_free")
    emit("rlwe/serve_fault_wasted_lanes", m_fault.healthy_reencryptions,
         f"{m_fault.quarantined_lanes}quarantined_"
         f"{m_fault.error_results}errors")
    return section


def _paillier_batch_section(rng) -> dict:
    """Vectorized-Paillier section: the RNS limb-array batch path
    (`repro.crypto.paillier_vec`, fixed-width residue channels +
    Montgomery GEMM kernels) vs the per-lane bignum object path
    (`repro.crypto.paillier`) on the encrypted re-rank, at batch 1 and 8.
    The batch-8 speedup is CI-gated at >= 3x by
    ``scripts/check_bench_regression.py`` (missing section = FAIL), along
    with bit-exact decrypted scores and zero silent object fallbacks at
    the benchmark key size."""
    import time

    from repro.crypto import paillier as pai
    from repro.crypto import paillier_vec as pvec

    key_bits, dim, kprime, big = 256, 384, 64, 8
    keys = [pai.keygen(key_bits, rng=np.random.default_rng(1000 + i))
            for i in range(big)]
    queries = _unit(rng, big, dim).astype(np.float64)
    cands = [_unit(rng, kprime, dim).astype(np.float64) for _ in range(big)]
    enc = [pai.encrypt_vector(k.pub, q, rng=np.random.default_rng(2000 + i))
           for i, (k, q) in enumerate(zip(keys, queries))]

    pvec.reset_counters()
    t0 = time.perf_counter()          # first call pays the jit compile
    warm = pvec.encrypted_scores_batch([k.pub for k in keys], enc, cands)
    compile_ms = (time.perf_counter() - t0) * 1e3

    # bit-exactness: the vectorized ciphertexts must decrypt to exactly
    # the object path's scores (both are exact integer arithmetic)
    obj_cts = [pai.encrypted_scores(k.pub, e, c)
               for k, e, c in zip(keys, enc, cands)]
    bit_exact = all(
        np.array_equal(pai.decrypt_scores(k, v), pai.decrypt_scores(k, o))
        for k, v, o in zip(keys, warm, obj_cts))
    assert bit_exact, "vectorized scores must decrypt bit-exact vs object"

    section = {"key_bits": key_bits, "dim": dim, "kprime": kprime,
               "compile_ms": compile_ms, "bit_exact": bit_exact}
    for bsz in (1, big):
        ks, es, cs = keys[:bsz], enc[:bsz], cands[:bsz]

        def object_path():
            for k, e, c in zip(ks, es, cs):
                pai.encrypted_scores(k.pub, e, c)

        def vectorized():
            pvec.encrypted_scores_batch([k.pub for k in ks], es, cs)

        object_us = timeit(object_path, repeat=2, warmup=0)
        vec_us = timeit(vectorized, repeat=3, warmup=1)
        speedup = object_us / vec_us
        emit(f"paillier/score_object_b{bsz}", object_us,
             f"kb={key_bits}_k'={kprime}")
        emit(f"paillier/score_vectorized_b{bsz}", vec_us,
             f"{speedup:.2f}x_vs_object")
        section[f"batch{bsz}"] = {
            "object_ms": object_us / 1e3,
            "vectorized_ms": vec_us / 1e3,
            "speedup_vectorized_vs_object": speedup,
        }
    section["object_fallback_lanes"] = pvec.counters["object"]
    section["vectorized_lanes"] = pvec.counters["vectorized"]
    emit("paillier/vectorized_fallbacks", section["object_fallback_lanes"],
         f"{section['vectorized_lanes']}vectorized_lanes")
    return section


def _ivf_routing_section(rng) -> dict:
    """IVF first-stage routing vs the flat scan at corpus scale (the
    ``ivf_routing`` section): a clustered 10^4-doc corpus (10^5 under
    REPRO_BENCH_FULL=1), top-k' through `cluster_topk` at the
    planner-derived ``nprobe`` vs `distributed_topk` over every row.
    CI gates (``scripts/check_bench_regression.py``, missing section =
    FAIL): routed >= 2x faster than flat, recall@k' == 1.0 at the planned
    probe bound, and the ``nprobe=all`` run bit-identical to the flat
    scan — the differential anchor that routing is a pure schedule
    change, not a scoring change."""
    from repro.retrieval.index import FlatIndex, IvfConfig
    from repro.retrieval.topk import (cluster_topk, distributed_topk,
                                      plan_nprobe)

    num_docs = 100_000 if FULL else 10_000
    dim, num_clusters, kprime, n_q = 256, 25, 32, 16
    # clustered corpus: equal-size tight clusters around random unit
    # centers — the regime IVF exists for (uniform-random rows have no
    # locality to route on, and no planner bound can fix that).  The
    # perturbation is a *unit* direction scaled to 0.1, so cluster radius
    # stays small at any dim (per-component gaussians would grow the
    # noise norm with sqrt(dim) and smear the clusters).
    centers = _unit(rng, num_clusters, dim)
    assign = np.repeat(np.arange(num_clusters), num_docs // num_clusters)
    emb = centers[assign] + 0.1 * _unit(rng, num_docs, dim)
    emb = (emb / np.linalg.norm(emb, axis=-1, keepdims=True)).astype(
        np.float32)
    index = FlatIndex.build(
        emb, normalize=False, ivf=IvfConfig(num_clusters=num_clusters))
    view = index.corpus_view()
    cm = view.cluster_map
    # queries concentrate on 4 hot topics (the repeat-tenant regime the
    # routed scan batches well: few distinct clusters per dispatch wave)
    hot = centers[np.repeat([0, 6, 12, 18], n_q // 4)]
    queries = hot + 0.1 * _unit(rng, n_q, dim)
    queries = (queries / np.linalg.norm(queries, axis=-1,
                                        keepdims=True)).astype(np.float32)

    nprobe = plan_nprobe(cm, kprime)
    flat = distributed_topk(index, queries, kprime)
    routed = cluster_topk(view, queries, kprime, nprobe=nprobe)
    flat_ids = np.asarray(flat.indices)
    routed_ids = np.asarray(routed.indices)
    recall = float(np.mean([
        len(set(flat_ids[b]) & set(routed_ids[b])) / kprime
        for b in range(n_q)]))
    # nprobe=all == flat scan, bit-identical (values and ids)
    full = cluster_topk(view, queries, kprime, nprobe=num_clusters)
    anchor = bool(
        np.array_equal(np.asarray(full.indices), flat_ids)
        and np.array_equal(np.asarray(full.values),
                           np.asarray(flat.values))
        and bool(full.exact))
    assert anchor, "nprobe=all must be bit-identical to the flat scan"

    def flat_scan():
        np.asarray(distributed_topk(index, queries, kprime).values)

    def routed_scan():
        np.asarray(cluster_topk(view, queries, kprime,
                                nprobe=nprobe).values)

    flat_us = timeit(flat_scan, repeat=9, warmup=2)
    routed_us = timeit(routed_scan, repeat=9, warmup=2)
    speedup = flat_us / routed_us
    rows_routed = int(np.max(cm.sizes[cm.route(queries, nprobe)]
                             .sum(axis=1)))
    emit("rlwe/ivf_flat_scan", flat_us, f"{num_docs}docs_k'={kprime}")
    emit("rlwe/ivf_routed_scan", routed_us,
         f"{speedup:.1f}x_vs_flat_nprobe={nprobe}")
    emit("rlwe/ivf_recall_at_kprime", recall * 100.0,
         f"rows<={rows_routed}/{num_docs}")
    return {
        "num_docs": num_docs,
        "dim": dim,
        "num_clusters": num_clusters,
        "kprime": kprime,
        "queries": n_q,
        "nprobe": nprobe,
        "flat_us": flat_us,
        "routed_us": routed_us,
        "speedup_routed_vs_flat": speedup,
        "recall_at_kprime": recall,
        "nprobe_all_bit_identical": anchor,
        "max_rows_routed": rows_routed,
    }


def _ingestion_section(params, rng) -> dict:
    """Streaming ingestion under live traffic (the ``ingestion`` section):
    a serving engine over the sharded candidate cache, with a tail-shard
    ingest (`FlatIndex.ingest` -> `ShardedCandidateCache.ingest_tail`)
    landing *between dispatch steps* of a draining stream.  The engine is
    pinned to its epoch-0 `CorpusView`, so every in-flight request must
    return bit-identical results to a no-ingest reference run — zero
    lost, zero bit-drift — while the cache's epoch advances underneath.
    After `refresh_corpus` the ingested rows are reachable.  All
    CI-gated (missing section = FAIL)."""
    import time

    from repro.retrieval.index import FlatIndex, IvfConfig
    from repro.serve import EngineConfig, ServeEngine
    from repro.serve.session import SessionManager

    dim, num_docs, n_new, n_req, max_batch = 64, 2048, 128, 16, 4
    shard_docs = 256
    emb = _unit(rng, num_docs, dim)
    docs = [f"doc-{i}".encode() for i in range(num_docs)]
    tail = _unit(rng, n_new, dim)
    queries = _unit(rng, n_req, dim)
    # shard-aligned IVF build: each 256-row cluster is exactly one cache
    # shard, so routing and residency speak the same ranges
    cfg = rlwe.CandidateCacheConfig(shard_docs=shard_docs)

    def build_engine():
        index = FlatIndex.build(
            emb, documents=docs, normalize=False,
            ivf=IvfConfig(num_clusters=num_docs // shard_docs,
                          align=shard_docs))
        eng = ServeEngine(
            index,
            config=EngineConfig(max_batch=max_batch, max_wait_s=30.0,
                                cache_config=cfg),
            sessions=SessionManager(rlwe_params=params,
                                    deterministic_seeds=True))
        for t in range(4):
            eng.open_session(f"bench-{t}", n=dim, N=num_docs, k=4,
                             radius=0.05, backend="rlwe")
        return eng

    def submit_all(eng):
        for i in range(n_req):
            eng.submit(f"bench-{i % 4}", queries[i],
                       key=jax.random.PRNGKey(i))

    ref_eng = build_engine()
    submit_all(ref_eng)
    want = {r.request_id: r for r in ref_eng.drain()}
    ref_eng.close()

    eng = build_engine()
    submit_all(eng)
    out = eng.step()        # first batch through: the lazy cache is live
    t0 = time.perf_counter()
    eng.cloud.index.ingest(tail, documents=[f"new-{i}".encode()
                                            for i in range(n_new)],
                           normalize=False)
    ingest_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    out += eng.drain()      # the rest of the stream rides the swap
    drain_us = (time.perf_counter() - t0) * 1e6
    stats = eng.cache_stats()

    lost = n_req - len(out)
    drift = sum(
        1 for r in out
        if not (r.ok and r.ids.tolist() == want[r.request_id].ids.tolist()
                and r.docs == want[r.request_id].docs
                and r.transcript.total_bytes
                == want[r.request_id].transcript.total_bytes))
    assert lost == 0 and drift == 0, \
        f"ingest under live traffic: lost={lost} drift={drift}"

    # epoch advance: after refresh the tail rows are reachable
    view = eng.refresh_corpus()
    eng.open_session("bench-fresh", n=dim, N=num_docs + n_new, k=4,
                     radius=0.05, backend="rlwe")
    probe = eng.submit("bench-fresh", tail[0],
                       key=jax.random.PRNGKey(10_000))
    post = eng.drain()
    reachable = any(r.request_id == probe
                    and any(int(i) >= num_docs for i in r.ids)
                    for r in post)
    assert reachable, "ingested rows must be servable after refresh"
    eng.close()

    section = {
        "num_docs": num_docs,
        "ingested_docs": n_new,
        "shard_docs": shard_docs,
        "requests": n_req,
        "max_batch": max_batch,
        "ingest_us": ingest_us,
        "drain_after_ingest_us": drain_us,
        "lost_requests": lost,
        "bit_drift_requests": drift,
        "epoch_before": 0,
        "epoch_after": int(view.epoch),
        "cache_ingests": int(stats["ingests"]) if stats else 0,
        "tail_reachable_after_refresh": reachable,
    }
    emit("rlwe/ingest_tail_swap", ingest_us,
         f"{n_new}docs_epoch{section['epoch_after']}")
    emit("rlwe/ingest_live_stream", drain_us,
         f"lost={lost}_drift={drift}")
    return section


def run() -> None:
    if FULL:
        params = rlwe.RlweParams()                    # N=4096, chunk=1024
        n_dim, num_docs, kprime = 3072, 20_000, 115   # paper Table 5 regime
    else:
        # n_dim=3072 (text-embedding-3-large, Table 5): 6 chunks per doc —
        # the regime where cold per-request packing + forward NTTs dominate
        params = rlwe.RlweParams(n_poly=1024, chunk=512)
        n_dim, num_docs, kprime = 3072, 512, 32
    rng = np.random.default_rng(0)
    docs = _unit(rng, num_docs, n_dim)
    sk = rlwe.keygen(params, rng)

    builds = []
    build_us = timeit(
        lambda: builds.append(rlwe.build_candidate_cache(params, docs)),
        repeat=1, warmup=0)
    cache = builds[0]
    emit("rlwe/cache_build", build_us,
         f"{cache.nbytes / 2**20:.1f}MiB/{num_docs}docs")

    results = {}
    for bsz in (1, 8):
        queries = _unit(rng, bsz, n_dim)
        q_cts = [rlwe.encrypt_query(sk, q, rng) for q in queries]
        ids = rng.integers(0, num_docs, size=(bsz, kprime))
        rows = docs[ids]

        def cold():
            packed = rlwe.pack_candidates_batch(params, rows)
            out = rlwe.encrypted_scores_batch_stacked(
                params, q_cts, packed, kprime, n_dim, use_pallas=False)
            jax.block_until_ready(out.c0)

        def cached():
            out = rlwe.encrypted_scores_cached_batch(
                params, q_cts, cache, ids, use_pallas=False)
            jax.block_until_ready(out.c0)

        def fused():
            out = rlwe.encrypted_scores_cached_batch(
                params, q_cts, cache, ids, use_pallas=True)
            jax.block_until_ready(out.c0)

        cold_us = timeit(cold, repeat=9, warmup=2)
        cached_us = timeit(cached, repeat=9, warmup=2)
        # interpret-mode Pallas off-TPU: correctness/overhead tracking only
        fused_us = timeit(fused, repeat=3)
        qps = bsz / (cached_us / 1e6)
        speedup = cold_us / cached_us
        emit(f"rlwe/score_cold_b{bsz}", cold_us, f"k'={kprime}")
        emit(f"rlwe/score_cached_b{bsz}", cached_us,
             f"{speedup:.1f}x_vs_cold")
        emit(f"rlwe/score_cached_fused_b{bsz}", fused_us,
             "interpret" if jax.default_backend() != "tpu" else "tpu")
        emit(f"rlwe/qps_cached_b{bsz}", cached_us, f"{qps:.1f}qps")
        results[f"batch{bsz}"] = {
            "cold_pack_us": cold_us,
            "cached_us": cached_us,
            "cached_fused_us": fused_us,
            "speedup_cached_vs_cold": speedup,
            "per_request_cold_us": cold_us / bsz,
            "per_request_cached_us": cached_us / bsz,
            "cached_qps": qps,
        }

    # -- corpus scale: dense device-resident vs sharded HBM-resident cache --
    big_docs = 100_000 if FULL else 10_000
    big = _unit(rng, big_docs, n_dim)
    big_builds = []
    big_build_us = timeit(
        lambda: big_builds.append(rlwe.build_candidate_cache(params, big)),
        repeat=1, warmup=0)
    dense_big = big_builds[0]
    emit("rlwe/dense_cache_build_10k", big_build_us,
         f"{dense_big.nbytes / 2**20:.0f}MiB/{big_docs}docs")
    num_shards = 16
    budget = dense_big.nbytes // 8           # room for 2 of the 16 shards
    # two access regimes, two configs:
    #  * uniform-random ids (the gated comparison): stream-only — pinning
    #    under uniform traffic is pure churn (a shard admission is a
    #    shard-sized host->device copy in the request path), so the right
    #    configuration gathers each request's k' rows on demand and keeps
    #    device memory at just the gather buffer;
    #  * skewed ids confined to explicitly pinned hot shards (the repeat-
    #    tenant case the LRU exists for): gathers run device-side.
    cfg_stream = rlwe.CandidateCacheConfig(num_shards=num_shards,
                                           max_resident_bytes=0)
    views = []
    view_us = timeit(
        lambda: views.append(rlwe.shard_candidate_cache(dense_big,
                                                        cfg_stream)),
        repeat=1, warmup=0)   # re-view of the retained host pool, no re-pack
    stream = views[0]
    emit("rlwe/sharded_view_10k", view_us, f"{stream.num_shards}shards")
    hot = rlwe.shard_candidate_cache(
        dense_big, rlwe.CandidateCacheConfig(
            num_shards=num_shards, max_resident_bytes=budget,
            pin_on_access=False))
    hot.pin(0)
    hot.pin(1)

    sharded = {
        "num_docs": big_docs,
        "num_shards": stream.num_shards,
        "shard_docs": stream.shard_docs,
        "dense_cache_bytes": dense_big.nbytes,
        "hot_budget_bytes": budget,
        "dense_cache_build_us": big_build_us,
        "shard_view_us": view_us,
    }
    for bsz in (1, 8):
        queries = _unit(rng, bsz, n_dim)
        q_cts = [rlwe.encrypt_query(sk, q, rng) for q in queries]
        ids = rng.integers(0, big_docs, size=(bsz, kprime))
        ids_hot = rng.integers(0, 2 * stream.shard_docs, size=(bsz, kprime))

        def dense_score(ids=ids):
            out = rlwe.encrypted_scores_cached_batch(
                params, q_cts, dense_big, ids, use_pallas=False)
            jax.block_until_ready(out.c0)

        def stream_score():
            out = rlwe.encrypted_scores_cached_batch(
                params, q_cts, stream, ids, use_pallas=False)
            jax.block_until_ready(out.c0)

        def hot_score():
            out = rlwe.encrypted_scores_cached_batch(
                params, q_cts, hot, ids_hot, use_pallas=False)
            jax.block_until_ready(out.c0)

        def gather_only():
            jax.block_until_ready(stream.gather(ids))

        dense_us = timeit(dense_score, repeat=9, warmup=2)
        sharded_us = timeit(stream_score, repeat=9, warmup=2)
        gather_us = timeit(gather_only, repeat=9, warmup=2)
        dense_hot_us = timeit(lambda: dense_score(ids_hot),
                              repeat=9, warmup=2)
        hot_us = timeit(hot_score, repeat=9, warmup=2)
        gather_buf = bsz * kprime * stream.num_chunks * \
            params.num_primes * params.n_poly * 4
        # peak device footprint of the gated (streaming) layout: no pinned
        # shards, just the transient per-request gather buffer
        peak = stream.peak_resident_bytes + gather_buf
        ratio = sharded_us / dense_us
        emit(f"rlwe/score_dense10k_b{bsz}", dense_us, f"k'={kprime}")
        emit(f"rlwe/score_sharded10k_b{bsz}", sharded_us,
             f"{ratio:.2f}x_vs_dense")
        emit(f"rlwe/gather_sharded10k_b{bsz}", gather_us,
             f"{gather_buf / 2**20:.1f}MiB/req")
        emit(f"rlwe/score_sharded_hot10k_b{bsz}", hot_us,
             f"{hot_us / dense_hot_us:.2f}x_vs_dense_pinned")
        sharded[f"batch{bsz}"] = {
            "dense_us": dense_us,
            "sharded_us": sharded_us,
            "gather_us": gather_us,
            "ratio_sharded_vs_dense": ratio,
            "dense_hot_us": dense_hot_us,
            "sharded_hot_us": hot_us,
            "ratio_hot_vs_dense": hot_us / dense_hot_us,
            "request_gather_bytes": gather_buf,
            "peak_sharded_bytes": peak,
            "memory_reduction_vs_dense": dense_big.nbytes / peak,
            "hot_peak_bytes": hot.peak_resident_bytes + gather_buf,
        }
    sharded["hot_lru"] = hot.stats()
    sharded["hot_lru"]["resident_shards"] = list(
        sharded["hot_lru"]["resident_shards"])
    emit("rlwe/sharded_peak_mem_mib",
         sharded["batch8"]["peak_sharded_bytes"] / 2**20,
         f"{sharded['batch8']['memory_reduction_vs_dense']:.1f}x_smaller"
         f"_than_dense")

    # -- both regimes under ONE default-policy config ------------------------
    # The async, frequency-aware admission policy (admit on 2nd touch inside
    # a decayed-counter window; H2D copy on the background admitter, off the
    # request path) is what lets a single CandidateCacheConfig serve both
    # access regimes: skewed ids admit their hot shards after one repeat and
    # then gather device-side, while uniform ids mostly stream (background
    # churn bounded by the admit queue) instead of paying a shard-sized
    # synchronous copy per miss.  CI gates both ratios under this one
    # config (scripts/check_bench_regression.py) so the synchronous-
    # admission churn regression can never come back.
    cfg_default = rlwe.CandidateCacheConfig(num_shards=num_shards,
                                            max_resident_bytes=budget)
    adaptive = rlwe.shard_candidate_cache(dense_big, cfg_default)
    default_cfg = {
        "num_shards": adaptive.num_shards,
        "hot_budget_bytes": budget,
        "async_admission": cfg_default.async_admission,
        "admit_threshold": cfg_default.admit_threshold,
    }
    bsz = 8
    queries = _unit(rng, bsz, n_dim)
    q_cts = [rlwe.encrypt_query(sk, q, rng) for q in queries]
    regime_ids = {
        "uniform": rng.integers(0, big_docs, size=(bsz, kprime)),
        "skewed": rng.integers(0, 2 * adaptive.shard_docs,
                               size=(bsz, kprime)),
    }
    for regime, ids in regime_ids.items():
        def dense_score():
            out = rlwe.encrypted_scores_cached_batch(
                params, q_cts, dense_big, ids, use_pallas=False)
            jax.block_until_ready(out.c0)

        def adaptive_score():
            # the serving engine's request shape: prefetch the admissions
            # as soon as the ids are known, then score (the gather streams
            # until the background swap lands — it never blocks)
            adaptive.prefetch(ids)
            out = rlwe.encrypted_scores_cached_batch(
                params, q_cts, adaptive, ids, use_pallas=False)
            jax.block_until_ready(out.c0)

        dense_us = timeit(dense_score, repeat=9, warmup=2)
        adaptive_us = timeit(adaptive_score, repeat=9, warmup=2)
        ratio = adaptive_us / dense_us
        emit(f"rlwe/score_default_cfg_{regime}10k_b{bsz}", adaptive_us,
             f"{ratio:.2f}x_vs_dense")
        default_cfg[regime] = {
            "dense_us": dense_us,
            "adaptive_us": adaptive_us,
            "ratio_vs_dense_b8": ratio,
        }
    adaptive.flush()
    stats = adaptive.stats()
    stats["resident_shards"] = list(stats["resident_shards"])
    default_cfg["stats"] = stats
    emit("rlwe/default_cfg_admissions", stats["async_admissions"],
         f"{stats['policy_deferrals']}deferred_"
         f"{stats['admit_dropped']}dropped")
    sharded["default_config"] = default_cfg
    results["sharded"] = sharded

    results["serve_faults"] = _serve_fault_section(params, rng)
    results["paillier_batch"] = _paillier_batch_section(rng)
    results["ivf_routing"] = _ivf_routing_section(rng)
    results["ingestion"] = _ingestion_section(params, rng)

    payload = {
        "bench": "rlwe_rerank",
        "backend": jax.default_backend(),
        "config": {"n_poly": params.n_poly, "num_primes": params.num_primes,
                   "chunk": params.chunk, "n_dim": n_dim,
                   "num_docs": num_docs, "kprime": kprime,
                   "cache_bytes": cache.nbytes,
                   "cache_build_us": build_us, "full": FULL},
        "results": results,
    }
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {OUT_PATH}", flush=True)


if __name__ == "__main__":
    run()
