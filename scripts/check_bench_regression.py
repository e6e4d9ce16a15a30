#!/usr/bin/env python3
"""CI gate on the encrypted re-rank perf trajectory.

Reads BENCH_rlwe.json (written by ``python -m benchmarks.run --only rlwe``)
and fails if

  * cached scoring is not faster than cold per-request packing at any
    recorded batch size, or
  * (when the corpus-scale section is present) sharded-gather scoring at
    batch 8 is more than ``max_sharded_ratio`` (default 1.3x) slower than
    dense-cache scoring, or the sharded layout's peak device footprint is
    not at least ``min_mem_reduction`` (default 4x) smaller than the dense
    cache, or
  * the single default-policy config (async, frequency-aware admission) is
    more than ``max_skewed_ratio`` (default 1.2x) slower than dense under
    skewed ids, or more than ``max_uniform_ratio`` (default 1.3x) slower
    under uniform ids, at batch 8 — the both-regimes guarantee: one config
    must never regress to synchronous-admission churn in either regime, or
  * (serve_faults section) lane-level fault isolation regressed: any
    healthy-lane re-encryption under a persistently poisoned lane (must be
    exactly 0), more/fewer error results than poisoned lanes, or batch
    occupancy under faults below ``min_occupancy_ratio`` (default 0.9) of
    the fault-free run, or
  * (paillier_batch section — missing section = FAIL) the vectorized
    RNS-limb Paillier batch path is less than ``min_paillier_speedup``
    (default 3.0x) faster than the per-lane object path at batch 8, its
    scores were not bit-exact against the object path, or lanes silently
    fell back to objects at the benchmark key size, or
  * (ivf_routing section — missing section = FAIL) the clustered
    first-stage scan is less than ``min_ivf_speedup`` (default 2.0x)
    faster than the flat scan, recall@k' at the planner-derived
    ``nprobe`` is below 1.0, or the ``nprobe=all`` run was not
    bit-identical to the flat scan, or
  * (ingestion section — missing section = FAIL) a live tail-shard
    ingest lost or bit-drifted any in-flight request, the cache recorded
    no ingest, or the corpus epoch failed to advance.

With ``--serve-json BENCH_serve.json`` (written by
``python -m benchmarks.serve_bench``) it additionally gates the serving
engine itself: batch-8 occupancy must reach ``--min-serve-occupancy``
(default 0.8), batch-8 QPS must beat sequential by
``--min-serve-speedup`` (default 1.0x), and the closed-loop overload
section must prove admission control works: zero lost requests at every
offered-load point (offered == completed + shed), goodput at 2x the
saturation knee >= ``--min-goodput-ratio`` (default 0.8) of goodput at
the knee, interactive p99 under 2x overload within the recorded
p99_bound, the 2x point actually shedding, and the unlimited config
measurably collapsing where the admission config holds.

The serve JSON must also carry the scale-out ``replica_sweep`` section
(missing section = FAIL): per-query parity vs the 1-replica run
re-checked, merge overhead bounded, 2-replica QPS >= 1.3x the 1-replica
run on hosts with >= 2 CPUs (on a 1-CPU host thread parallelism is
physically unavailable, so the gate bounds router overhead at >= 0.8x
instead), 4-replica QPS >= 2.0x on hosts with >= 4 CPUs, and the
replica-failure fault point losing zero requests
(offered == returned; ledger submitted == completed +
quarantine-resolved).

The serve JSON must also carry the ``retry_lane`` section (missing
section = FAIL): with quarantine solo retries running on the background
retry lane, the healthy requests' p99 under transient faults must stay
within ``--max-retry-p99-ratio`` (default 1.5) of the fault-free run,
with zero lost requests and the retries actually exercised.

    scripts/check_bench_regression.py [BENCH_rlwe.json] [min_speedup=1.0]
        [max_sharded_ratio=1.3] [min_mem_reduction=4.0]
        [max_skewed_ratio=1.2] [max_uniform_ratio=1.3]
        [min_occupancy_ratio=0.9]
        [--serve-json BENCH_serve.json] [--min-serve-speedup 1.0]
        [--min-serve-occupancy 0.8]
"""

from __future__ import annotations

import argparse
import json
import sys


def _check_cached_vs_cold(results: dict, min_speedup: float) -> int:
    failures = 0
    checked = 0
    for name in sorted(results):
        if not name.startswith("batch"):
            continue
        checked += 1
        row = results[name]
        speedup = row.get("speedup_cached_vs_cold")
        if speedup is None or speedup < min_speedup:
            print(f"FAIL {name}: cached speedup {speedup} < {min_speedup} "
                  f"(cold {row.get('cold_pack_us')}us, "
                  f"cached {row.get('cached_us')}us)", file=sys.stderr)
            failures += 1
        else:
            print(f"ok   {name}: cached {speedup:.2f}x faster than cold "
                  f"({row.get('cached_us'):.0f}us vs "
                  f"{row.get('cold_pack_us'):.0f}us)")
    if not checked:      # a results-key rename must not silently pass CI
        print("FAIL: no batch* rows found — cached-vs-cold gate did not "
              "run", file=sys.stderr)
        failures += 1
    return failures


def _check_sharded(sharded: dict, max_ratio: float,
                   min_mem_reduction: float) -> int:
    row = sharded.get("batch8")
    if row is None:
        print("FAIL sharded: no batch8 row", file=sys.stderr)
        return 1
    failures = 0
    ratio = row.get("ratio_sharded_vs_dense")
    if ratio is None or ratio > max_ratio:
        print(f"FAIL sharded/batch8: sharded scoring {ratio}x dense "
              f"> {max_ratio}x "
              f"(dense {row.get('dense_us')}us, "
              f"sharded {row.get('sharded_us')}us)", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   sharded/batch8: sharded within {ratio:.2f}x of dense "
              f"({row.get('sharded_us'):.0f}us vs "
              f"{row.get('dense_us'):.0f}us at "
              f"{sharded.get('num_docs')} docs)")
    red = row.get("memory_reduction_vs_dense")
    if red is None or red < min_mem_reduction:
        print(f"FAIL sharded/batch8: peak memory reduction {red}x "
              f"< {min_mem_reduction}x "
              f"(dense {sharded.get('dense_cache_bytes')}B, "
              f"sharded peak {row.get('peak_sharded_bytes')}B)",
              file=sys.stderr)
        failures += 1
    else:
        print(f"ok   sharded/batch8: peak cache memory {red:.1f}x smaller "
              f"than dense "
              f"({row.get('peak_sharded_bytes') / 2**20:.0f}MiB vs "
              f"{sharded.get('dense_cache_bytes') / 2**20:.0f}MiB)")
    return failures


def _check_default_config(sharded: dict, max_skewed: float,
                          max_uniform: float) -> int:
    """Both-regimes gate for the ONE default-policy config: a sharded-cache
    JSON without this section fails (the gate must not silently pass after
    a results-key rename), as does either regime's batch-8 ratio."""
    section = sharded.get("default_config")
    if section is None:
        print("FAIL default_config: sharded results lack the both-regimes "
              "section — the one-config gate did not run", file=sys.stderr)
        return 1
    failures = 0
    for regime, bound in (("skewed", max_skewed), ("uniform", max_uniform)):
        row = section.get(regime, {})
        ratio = row.get("ratio_vs_dense_b8")
        if ratio is None or ratio > bound:
            print(f"FAIL default_config/{regime}: batch-8 scoring {ratio}x "
                  f"dense > {bound}x under the default admission policy "
                  f"(dense {row.get('dense_us')}us, "
                  f"adaptive {row.get('adaptive_us')}us) — async/"
                  f"frequency-aware admission has regressed to request-path "
                  f"churn", file=sys.stderr)
            failures += 1
        else:
            print(f"ok   default_config/{regime}: one-config batch-8 "
                  f"within {ratio:.2f}x of dense "
                  f"({row.get('adaptive_us'):.0f}us vs "
                  f"{row.get('dense_us'):.0f}us)")
    return failures


def _check_serve_faults(section: dict, min_occupancy_ratio: float) -> int:
    """Lane-isolation gate: under one persistently poisoned lane in a
    batch of 8, no healthy lane may be re-encrypted, exactly the poisoned
    lanes may error, and batch occupancy must stay within
    ``min_occupancy_ratio`` of the fault-free run.  A JSON without the
    section fails — the gate must not silently pass after a results-key
    rename."""
    if section is None:
        print("FAIL serve_faults: results lack the fault-injection section "
              "— the lane-isolation gate did not run", file=sys.stderr)
        return 1
    failures = 0
    reenc = section.get("healthy_lane_reencryptions")
    if reenc != 0:
        print(f"FAIL serve_faults: {reenc} healthy-lane re-encryptions "
              f"under faults (must be exactly 0 — quarantine is leaking "
              f"work back onto healthy lanes)", file=sys.stderr)
        failures += 1
    else:
        print("ok   serve_faults: 0 healthy-lane re-encryptions under a "
              "persistently poisoned lane")
    errors = section.get("error_results")
    poisoned = section.get("poisoned_lanes")
    if errors != poisoned:
        print(f"FAIL serve_faults: {errors} error results for {poisoned} "
              f"poisoned lanes (quarantine must error exactly the poisoned "
              f"lanes)", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   serve_faults: exactly {poisoned} error result(s) for "
              f"{poisoned} poisoned lane(s)")
    ratio = section.get("occupancy_ratio")
    if ratio is None or ratio < min_occupancy_ratio:
        print(f"FAIL serve_faults: batch occupancy under faults is {ratio}x "
              f"the fault-free run < {min_occupancy_ratio}x "
              f"(faulty {section.get('occupancy_faulty')}, fault-free "
              f"{section.get('occupancy_fault_free')})", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   serve_faults: occupancy {ratio:.2f}x of fault-free "
              f"({section.get('occupancy_faulty'):.3f} vs "
              f"{section.get('occupancy_fault_free'):.3f} at batch "
              f"{section.get('max_batch')})")
    return failures


def _check_paillier_batch(section: dict, min_speedup_b8: float = 3.0) -> int:
    """Vectorized-Paillier gate: the RNS limb-array batch path must beat
    the per-lane object path by ``min_speedup_b8``x at batch 8, the
    recorded scores must have decrypted bit-exact against the object
    path, and no lane may have silently fallen back to objects at the
    benchmark's key size.  A JSON without the section fails — the gate
    must not silently pass after a results-key rename."""
    if section is None:
        print("FAIL paillier_batch: results lack the vectorized-Paillier "
              "section — the batch-crypto gate did not run",
              file=sys.stderr)
        return 1
    failures = 0
    speedup = section.get("batch8", {}).get("speedup_vectorized_vs_object")
    if speedup is None or speedup < min_speedup_b8:
        print(f"FAIL paillier_batch: batch-8 vectorized scoring "
              f"{speedup}x the object path < {min_speedup_b8}x "
              f"(object {section.get('batch8', {}).get('object_ms')}ms, "
              f"vectorized "
              f"{section.get('batch8', {}).get('vectorized_ms')}ms)",
              file=sys.stderr)
        failures += 1
    else:
        b8 = section["batch8"]
        print(f"ok   paillier_batch: batch-8 vectorized {speedup:.2f}x "
              f"the object path ({b8.get('vectorized_ms'):.0f}ms vs "
              f"{b8.get('object_ms'):.0f}ms at kb="
              f"{section.get('key_bits')})")
    b1 = section.get("batch1", {})
    s1 = b1.get("speedup_vectorized_vs_object")
    if s1 is None:
        print("FAIL paillier_batch: no batch-1 row", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   paillier_batch: batch-1 vectorized {s1:.2f}x the "
              f"object path (recorded, ungated)")
    if not section.get("bit_exact"):
        print("FAIL paillier_batch: vectorized scores did not decrypt "
              "bit-exact against the object path", file=sys.stderr)
        failures += 1
    else:
        print("ok   paillier_batch: decrypted scores bit-exact vs the "
              "object path")
    fell_back = section.get("object_fallback_lanes", 0)
    if fell_back:
        print(f"FAIL paillier_batch: {fell_back} lane(s) silently fell "
              f"back to the object path at the benchmark key size",
              file=sys.stderr)
        failures += 1
    else:
        print("ok   paillier_batch: 0 object-path fallbacks at the "
              "benchmark key size")
    return failures


def _check_ivf_routing(section: dict, min_speedup: float = 2.0) -> int:
    """IVF first-stage routing gate: the routed scan must beat the flat
    scan by ``min_speedup``x at the bench corpus size, recall@k' at the
    planner-derived ``nprobe`` must be exactly 1.0 (the Theorem-1 bound
    covers the probed clusters), and the ``nprobe=all`` run must have
    been bit-identical to the flat scan — routing is a schedule change,
    never a scoring change.  A JSON without the section fails — the gate
    must not silently pass after a results-key rename."""
    if section is None:
        print("FAIL ivf_routing: results lack the IVF routing section — "
              "the first-stage routing gate did not run", file=sys.stderr)
        return 1
    failures = 0
    speedup = section.get("speedup_routed_vs_flat")
    if speedup is None or speedup < min_speedup:
        print(f"FAIL ivf_routing: routed scan {speedup}x the flat scan "
              f"< {min_speedup}x at {section.get('num_docs')} docs "
              f"(flat {section.get('flat_us')}us, routed "
              f"{section.get('routed_us')}us)", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   ivf_routing: routed scan {speedup:.2f}x the flat "
              f"scan at {section.get('num_docs')} docs "
              f"(nprobe={section.get('nprobe')})")
    recall = section.get("recall_at_kprime")
    if recall is None or recall < 1.0:
        print(f"FAIL ivf_routing: recall@k' {recall} < 1.0 at the "
              f"planner-derived nprobe={section.get('nprobe')} — the "
              f"probe bound no longer covers the planned search range",
              file=sys.stderr)
        failures += 1
    else:
        print(f"ok   ivf_routing: recall@k' == 1.0 at the planned "
              f"nprobe={section.get('nprobe')} "
              f"(k'={section.get('kprime')})")
    if not section.get("nprobe_all_bit_identical"):
        print("FAIL ivf_routing: nprobe=all was not bit-identical to the "
              "flat scan — the differential anchor broke",
              file=sys.stderr)
        failures += 1
    else:
        print("ok   ivf_routing: nprobe=all bit-identical to the flat "
              "scan")
    return failures


def _check_ingestion(section: dict) -> int:
    """Streaming-ingestion gate: a tail-shard ingest landing mid-stream
    must lose zero in-flight requests and bit-drift zero results (the
    serving engine stays pinned to its epoch-0 view), the sharded cache
    must have recorded the ingest, the corpus epoch must have advanced,
    and the ingested rows must have been reachable after
    ``refresh_corpus``.  A JSON without the section fails — the gate
    must not silently pass after a results-key rename."""
    if section is None:
        print("FAIL ingestion: results lack the streaming-ingestion "
              "section — the live tail-shard swap gate did not run",
              file=sys.stderr)
        return 1
    failures = 0
    lost = section.get("lost_requests")
    drift = section.get("bit_drift_requests")
    if lost != 0 or drift != 0:
        print(f"FAIL ingestion: live tail-shard swap lost {lost} and "
              f"bit-drifted {drift} of {section.get('requests')} "
              f"in-flight requests (both must be 0)", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   ingestion: {section.get('requests')} in-flight "
              f"requests across the swap, 0 lost, 0 bit-drifted")
    if section.get("cache_ingests", 0) < 1:
        print("FAIL ingestion: the sharded cache recorded no tail-shard "
              "ingest — the swap never reached the cache",
              file=sys.stderr)
        failures += 1
    elif section.get("epoch_after", 0) <= section.get("epoch_before", 0):
        print(f"FAIL ingestion: corpus epoch did not advance "
              f"({section.get('epoch_before')} -> "
              f"{section.get('epoch_after')})", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   ingestion: cache ingests="
              f"{section.get('cache_ingests')}, epoch "
              f"{section.get('epoch_before')} -> "
              f"{section.get('epoch_after')}")
    if not section.get("tail_reachable_after_refresh"):
        print("FAIL ingestion: ingested rows were not servable after "
              "refresh_corpus", file=sys.stderr)
        failures += 1
    else:
        print("ok   ingestion: ingested rows servable after "
              "refresh_corpus")
    return failures


def _check_overload(results: dict, min_goodput_ratio: float = 0.8) -> int:
    """Overload gate on the closed-loop offered-load sweep: admission
    control must keep goodput flat and interactive p99 bounded past the
    saturation knee, account for every offered request (zero lost), and
    beat the unlimited configuration it exists to replace.  A JSON
    without the section fails — the gate must not silently pass after a
    results-key rename."""
    section = results.get("overload")
    if section is None:
        print("FAIL overload: serve results lack the offered-load sweep "
              "section — the admission-control gate did not run",
              file=sys.stderr)
        return 1
    failures = 0
    points = section.get("points", {})
    for label in ("0.5x", "1x", "2x", "2x_unlimited"):
        point = points.get(label)
        if point is None:
            print(f"FAIL overload: missing point {label}", file=sys.stderr)
            failures += 1
            continue
        lost = point.get("lost")
        balanced = (point.get("offered")
                    == point.get("completed", 0) + point.get("shed", 0))
        if lost != 0 or not balanced:
            print(f"FAIL overload/{label}: {lost} lost requests, offered "
                  f"{point.get('offered')} != completed "
                  f"{point.get('completed')} + shed {point.get('shed')} — "
                  f"requests are being dropped silently", file=sys.stderr)
            failures += 1
        else:
            print(f"ok   overload/{label}: offered {point['offered']} == "
                  f"completed {point['completed']} + shed {point['shed']} "
                  f"(0 lost)")
    knee = points.get("1x", {})
    two_x = points.get("2x", {})
    unlimited = points.get("2x_unlimited", {})
    g1, g2 = knee.get("goodput_qps"), two_x.get("goodput_qps")
    if g1 is None or g2 is None or g2 < min_goodput_ratio * g1:
        print(f"FAIL overload: goodput at 2x saturation {g2} qps < "
              f"{min_goodput_ratio}x of the knee's {g1} qps — admission "
              f"control no longer holds goodput past the knee",
              file=sys.stderr)
        failures += 1
    else:
        print(f"ok   overload: goodput holds past the knee "
              f"({g2:.2f} qps at 2x vs {g1:.2f} qps at 1x, "
              f">= {min_goodput_ratio}x)")
    bound = section.get("p99_bound_s")
    p99 = two_x.get("p99_interactive_s")
    if bound is None or p99 is None or p99 > bound:
        print(f"FAIL overload: interactive p99 at 2x is {p99}s, above the "
              f"recorded bound {bound}s — interactive traffic is no "
              f"longer protected under overload", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   overload: interactive p99 {p99:.3f}s <= bound "
              f"{bound:.3f}s at 2x offered load")
    if two_x.get("shed", 0) <= 0:
        print("FAIL overload: the 2x point shed nothing — the sweep is "
              "not actually overloading the engine", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   overload: 2x point shed {two_x['shed']} requests "
              f"({two_x.get('shed_by_reason')})")
    # the point of the tier: at the same 2x offered load the unlimited
    # config must do measurably worse — lower goodput (queue-wait
    # latency eats the deadlines) or a blown p99
    g_unl = unlimited.get("goodput_qps")
    p99_unl = unlimited.get("p99_interactive_s")
    collapsed = ((g_unl is not None and g2 is not None and g_unl < g2)
                 or (p99_unl is not None and bound is not None
                     and p99_unl > bound))
    if not collapsed:
        print(f"FAIL overload: unlimited config did not collapse at 2x "
              f"(goodput {g_unl} vs admission {g2}, p99 {p99_unl}s vs "
              f"bound {bound}s) — the sweep no longer demonstrates the "
              f"admission win", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   overload: unlimited config collapses at 2x "
              f"(goodput {g_unl:.2f} vs {g2:.2f} qps, p99 "
              f"{p99_unl:.3f}s vs bound {bound:.3f}s)")
    return failures


def _check_replica_sweep(results: dict, min_scaling: float = 1.3,
                         max_overhead_ratio: float = 0.8,
                         max_merge_frac: float = 0.25,
                         min_scaling4: float = 2.0) -> int:
    """Scale-out gate on the replica sweep: the section must exist (a
    results-key rename must not silently drop the scale-out contract),
    the sweep must have re-checked per-query parity against the
    1-replica run, the merge must stay cheap, and the fault point must
    account for every request — zero lost.

    The QPS bound is physical: replica drains and slice scans run on
    separate worker threads, so on a host with >= 2 CPUs the 2-replica
    run must reach ``min_scaling``x the 1-replica QPS.  A 1-CPU host
    cannot parallelize threads at all — there the gate instead bounds
    the router's overhead (scatter + merge + ledger must not cost more
    than ``1 - max_overhead_ratio`` of single-engine throughput).  On a
    host with >= 4 CPUs the 4-replica point is armed too: it must reach
    ``min_scaling4``x the 1-replica QPS (four drains genuinely in
    flight, not just two)."""
    section = results.get("replica_sweep")
    if section is None:
        print("FAIL replica_sweep: serve results lack the replica-sweep "
              "section — the scale-out gate did not run", file=sys.stderr)
        return 1
    failures = 0
    if not section.get("parity_checked"):
        print("FAIL replica_sweep: per-query parity vs the 1-replica run "
              "was not checked", file=sys.stderr)
        failures += 1
    points = section.get("points", {})
    for label in ("1", "2", "4"):
        if label not in points:
            print(f"FAIL replica_sweep: missing point at {label} replicas",
                  file=sys.stderr)
            failures += 1
    if failures:
        return failures
    q1 = points["1"].get("qps")
    q2 = points["2"].get("qps")
    cpus = section.get("host_cpus")
    if q1 is None or q2 is None:
        print("FAIL replica_sweep: points lack qps", file=sys.stderr)
        failures += 1
    elif cpus is not None and cpus >= 2:
        if q2 < min_scaling * q1:
            print(f"FAIL replica_sweep: 2-replica qps {q2:.3f} < "
                  f"{min_scaling}x the 1-replica {q1:.3f} on a "
                  f"{cpus}-CPU host — scale-out is not scaling",
                  file=sys.stderr)
            failures += 1
        else:
            print(f"ok   replica_sweep: 2 replicas {q2 / q1:.2f}x the "
                  f"1-replica qps (>= {min_scaling}x, {cpus} CPUs)")
    else:
        # single-CPU host: thread parallelism is physically unavailable,
        # so gate the router's overhead instead of the scaling win
        if q2 < max_overhead_ratio * q1:
            print(f"FAIL replica_sweep: 2-replica qps {q2:.3f} < "
                  f"{max_overhead_ratio}x the 1-replica {q1:.3f} on a "
                  f"1-CPU host — router overhead regressed",
                  file=sys.stderr)
            failures += 1
        else:
            print(f"note replica_sweep: 1-CPU host ({cpus}) — the "
                  f"{min_scaling}x scaling gate needs >= 2 CPUs; gating "
                  f"overhead instead")
            print(f"ok   replica_sweep: 2 replicas {q2 / q1:.2f}x the "
                  f"1-replica qps (>= {max_overhead_ratio}x overhead "
                  f"bound)")
    q4 = points["4"].get("qps")
    if cpus is not None and cpus >= 4:
        if q1 is None or q4 is None or q4 < min_scaling4 * q1:
            print(f"FAIL replica_sweep: 4-replica qps {q4} < "
                  f"{min_scaling4}x the 1-replica {q1} on a "
                  f"{cpus}-CPU host — scale-out stops paying past 2 "
                  f"replicas", file=sys.stderr)
            failures += 1
        else:
            print(f"ok   replica_sweep: 4 replicas {q4 / q1:.2f}x the "
                  f"1-replica qps (>= {min_scaling4}x, {cpus} CPUs)")
    else:
        print(f"note replica_sweep: host has {cpus} CPU(s) — the "
              f"{min_scaling4}x 4-replica gate arms at >= 4 CPUs")
    merge_ok = True
    for label, point in sorted(points.items()):
        frac = point.get("merge_frac")
        if frac is None or frac > max_merge_frac:
            print(f"FAIL replica_sweep/{label}: merge overhead {frac} of "
                  f"wall > {max_merge_frac}", file=sys.stderr)
            failures += 1
            merge_ok = False
    if merge_ok:
        print(f"ok   replica_sweep: merge overhead <= {max_merge_frac} "
              f"of wall at every point")
    fault = section.get("fault")
    if fault is None:
        print("FAIL replica_sweep: no fault point — the zero-lost "
              "contract under replica failure is untested",
              file=sys.stderr)
        return failures + 1
    lost = fault.get("lost")
    returned = fault.get("returned")
    offered = fault.get("offered")
    resolved = fault.get("quarantine_resolved", 0)
    submitted = sum(fault.get("submitted", []))
    completed = sum(fault.get("completed", []))
    if (lost != 0 or returned != offered
            or submitted != completed + resolved):
        print(f"FAIL replica_sweep/fault: {lost} lost, returned "
              f"{returned} of {offered} offered, ledger "
              f"{submitted} != {completed} + {resolved} — requests are "
              f"being dropped silently under replica failure",
              file=sys.stderr)
        failures += 1
    else:
        print(f"ok   replica_sweep/fault: offered {offered} == returned "
              f"{returned}, ledger {submitted} == {completed} completed "
              f"+ {resolved} quarantine-resolved (0 lost)")
    if not fault.get("quarantines"):
        print("FAIL replica_sweep/fault: no quarantine recorded — the "
              "injected fault did not fire", file=sys.stderr)
        failures += 1
    return failures


def _check_retry_lane(section: dict, max_p99_ratio: float = 1.5) -> int:
    """Retry-lane gate on the serve JSON: with quarantine solo retries on
    the background lane, the healthy requests' p99 under transient faults
    must stay within ``max_p99_ratio`` of the fault-free run — retries
    must not stall the dispatch thread — with zero lost requests and the
    retries actually exercised.  A JSON without the section fails — the
    gate must not silently pass after a results-key rename."""
    if section is None:
        print("FAIL retry_lane: serve results lack the retry-lane "
              "section — the healthy-batch p99 gate did not run",
              file=sys.stderr)
        return 1
    failures = 0
    ratio = section.get("healthy_p99_ratio_vs_fault_free")
    if ratio is None or ratio > max_p99_ratio:
        print(f"FAIL retry_lane: healthy p99 under faults {ratio}x the "
              f"fault-free run > {max_p99_ratio}x "
              f"(lane {section.get('p99_healthy_retry_lane_s')}s vs "
              f"fault-free {section.get('p99_fault_free_s')}s) — "
              f"retries are stalling the dispatch thread",
              file=sys.stderr)
        failures += 1
    else:
        inline = section.get("healthy_p99_ratio_vs_inline")
        print(f"ok   retry_lane: healthy p99 {ratio:.2f}x fault-free "
              f"(<= {max_p99_ratio}x; {inline:.2f}x the inline-retry "
              f"pass, recorded ungated)")
    if section.get("lost_requests") != 0:
        print(f"FAIL retry_lane: {section.get('lost_requests')} requests "
              f"lost under transient faults", file=sys.stderr)
        failures += 1
    if section.get("retried_requests_lane", 0) < 1:
        print("FAIL retry_lane: no retries recorded — the fault "
              "injection did not exercise the lane", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   retry_lane: {section.get('retried_requests_lane')} "
              f"solo retries off the dispatch thread, 0 lost")
    return failures


def _check_serve(path: str, min_speedup: float,
                 min_occupancy: float, min_goodput_ratio: float,
                 max_retry_p99_ratio: float = 1.5) -> int:
    """Serving-engine gate on BENCH_serve.json: batch-8 fill and the
    batched-vs-sequential throughput win."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        print(f"FAIL: cannot read {path}: {e}", file=sys.stderr)
        return 1
    results = data.get("results", {})
    big = results.get("big_batch", 8)
    row = results.get(f"batch{big}")
    if row is None:
        print(f"FAIL serve: no batch{big} row in {path}", file=sys.stderr)
        return 1
    failures = 0
    speedup = row.get("speedup_vs_sequential")
    if speedup is None or speedup < min_speedup:
        print(f"FAIL serve/batch{big}: batched qps {speedup}x sequential "
              f"< {min_speedup}x (qps {row.get('qps')})", file=sys.stderr)
        failures += 1
    else:
        print(f"ok   serve/batch{big}: batched {speedup:.2f}x sequential "
              f"qps ({row.get('qps'):.3f} qps)")
    occ = row.get("occupancy")
    if occ is None or occ < min_occupancy:
        print(f"FAIL serve/batch{big}: occupancy {occ} < {min_occupancy} "
              f"(batching is dispatching underfilled slots)",
              file=sys.stderr)
        failures += 1
    else:
        print(f"ok   serve/batch{big}: occupancy {occ:.2f} "
              f"(>= {min_occupancy})")
    failures += _check_overload(results, min_goodput_ratio)
    failures += _check_replica_sweep(results)
    failures += _check_retry_lane(results.get("retry_lane"),
                                  max_retry_p99_ratio)
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(
        description="CI gate on BENCH_rlwe.json (and optionally "
                    "BENCH_serve.json) perf/contract sections.")
    # positionals keep the historical argv layout working
    ap.add_argument("path", nargs="?", default="BENCH_rlwe.json")
    ap.add_argument("min_speedup", nargs="?", type=float, default=1.0)
    ap.add_argument("max_sharded_ratio", nargs="?", type=float, default=1.3)
    ap.add_argument("min_mem_reduction", nargs="?", type=float, default=4.0)
    ap.add_argument("max_skewed_ratio", nargs="?", type=float, default=1.2)
    ap.add_argument("max_uniform_ratio", nargs="?", type=float, default=1.3)
    ap.add_argument("min_occupancy_ratio", nargs="?", type=float,
                    default=0.9)
    ap.add_argument("--serve-json", default=None, metavar="PATH",
                    help="also gate BENCH_serve.json (serving-engine "
                         "occupancy + batched-vs-sequential QPS)")
    ap.add_argument("--min-serve-speedup", type=float, default=1.0)
    ap.add_argument("--min-serve-occupancy", type=float, default=0.8)
    ap.add_argument("--min-goodput-ratio", type=float, default=0.8,
                    help="overload gate: goodput at 2x saturation must be "
                         "at least this fraction of goodput at the knee")
    ap.add_argument("--min-paillier-speedup", type=float, default=3.0,
                    help="paillier_batch gate: vectorized RNS scoring at "
                         "batch 8 must beat the per-lane object path by "
                         "this factor")
    ap.add_argument("--min-ivf-speedup", type=float, default=2.0,
                    help="ivf_routing gate: the routed first-stage scan "
                         "must beat the flat scan by this factor")
    ap.add_argument("--max-retry-p99-ratio", type=float, default=1.5,
                    help="retry_lane gate: healthy-request p99 under "
                         "transient faults (background retry lane on) "
                         "must stay within this ratio of the fault-free "
                         "run")
    args = ap.parse_args()
    try:
        with open(args.path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:   # missing file or truncated JSON
        print(f"FAIL: cannot read {args.path}: {e}", file=sys.stderr)
        return 2
    results = data.get("results", {})
    if not results:
        print(f"FAIL: {args.path} has no results", file=sys.stderr)
        return 2
    failures = _check_cached_vs_cold(results, args.min_speedup)
    sharded = results.get("sharded")
    if sharded is not None:
        failures += _check_sharded(sharded, args.max_sharded_ratio,
                                   args.min_mem_reduction)
        failures += _check_default_config(sharded, args.max_skewed_ratio,
                                          args.max_uniform_ratio)
    else:
        print("note: no sharded section in results (pre-sharded-cache "
              "JSON); skipping the sharded gates")
    failures += _check_serve_faults(results.get("serve_faults"),
                                    args.min_occupancy_ratio)
    failures += _check_paillier_batch(results.get("paillier_batch"),
                                      args.min_paillier_speedup)
    failures += _check_ivf_routing(results.get("ivf_routing"),
                                   args.min_ivf_speedup)
    failures += _check_ingestion(results.get("ingestion"))
    if args.serve_json is not None:
        failures += _check_serve(args.serve_json, args.min_serve_speedup,
                                 args.min_serve_occupancy,
                                 args.min_goodput_ratio,
                                 args.max_retry_p99_ratio)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
