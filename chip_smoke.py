#!/usr/bin/env python3
"""On-chip smoke test: the private retrieval service, served on one TPU.

    python chip_smoke.py [--seed S]      # the served RLWE path (default)
    python chip_smoke.py --paillier      # Paillier's f64 channels: refused

Default run, one process, in order:

  1. kernels  each Pallas kernel of the served path against its XLA
              reference on the chip, at the served shapes (NTT forward /
              inverse / pointwise at batch 8 and 3, fused re-rank + iNTT
              at 39 result ciphertexts, score-top-k' over the corpus):
              integer kernels bit-identical, top-k' ids identical.
  2. lowering which kernels the lowered steps carry (`tpu_custom_call`).
  3. serve    16 requests from 4 tenants (max batch 8) through
              `repro.launch.serve` over 10^5 synthetic 768-dim docs (k=5,
              radius 0.03 -> k'=154), default RlweParams and the dense
              candidate cache: a cold and a warm run on the Pallas kernels,
              then the same requests on the XLA references.  Every request
              must be ok (no quarantine, error or shed), its top-k ids must
              be the exact numpy top-k, and ids, docs and wire bytes must
              be identical across the three runs.  Device memory (in use, and the
              peak so far) is logged after each step.
  4. layout   the compiled dense scoring step at the served shapes reads the
              pool's rows in place: no instruction but a parameter has a
              result with one row per document (a relayout of the pool
              would copy all of it on every call).

It fails (exit 1, no result line) unless JAX's first device is a TPU.  The
last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
It uses JAX's persistent compilation cache like the serve launcher
(``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``) and starts no
other process.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DOCS, DIM, K, RADIUS = 100_000, 768, 5, 0.03
KPRIME = 154           # what the planner derives for (DOCS, DIM, K, RADIUS)
REQUESTS, TENANTS, MAX_BATCH = 16, 4, 8


def log(**record) -> None:
    print(json.dumps(record), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def kernels_phase(index, rng) -> None:
    """Pallas kernels vs XLA references, on the chip, at served shapes."""
    import numpy as np
    import jax.numpy as jnp

    from repro.crypto import rlwe
    from repro.kernels.ntt import ops as ntt_ops
    from repro.kernels.ntt import ref as ntt_ref
    from repro.kernels.scoretopk import ops as sops

    params = rlwe.RlweParams()
    n = params.n_poly
    cpt = params.cands_per_ct(DIM)
    num_ct = -(-KPRIME // cpt)
    out = {}
    for i, ctx in enumerate(params.ctxs):
        for batch in (8, 3):
            x = jnp.asarray(ntt_ref.random_poly(rng, (batch, n), ctx.q))
            y = jnp.asarray(ntt_ref.random_poly(rng, (batch, n), ctx.q))
            for name, fn in (
                    ("ntt_fwd", lambda up: ntt_ops.ntt_fwd(
                        x, ctx, use_pallas=up)),
                    ("ntt_inv", lambda up: ntt_ops.ntt_inv(
                        x, ctx, use_pallas=up)),
                    ("ntt_pointwise_mul", lambda up: ntt_ops.pointwise_mul(
                        x, y, ctx, use_pallas=up))):
                same = bool(np.array_equal(np.asarray(fn(True)),
                                           np.asarray(fn(False))))
                out[f"{name}[p{i},b{batch}]"] = same
        polys = jnp.asarray(ntt_ref.random_poly(rng, (8, num_ct, cpt, n),
                                                ctx.q))
        f0 = jnp.asarray(ntt_ref.random_poly(rng, (8, 1, n), ctx.q))
        f1 = jnp.asarray(ntt_ref.random_poly(rng, (8, 1, n), ctx.q))
        tw = rlwe._slot_twiddles(params, DIM)[i]
        got = ntt_ops.fused_rotate_hadamard_intt(polys, tw, f0, f1, ctx,
                                                 use_pallas=True)
        want = ntt_ops.fused_rotate_hadamard_intt(polys, tw, f0, f1, ctx,
                                                  use_pallas=False)
        out[f"rerank_fused_intt[p{i}]"] = all(
            bool(np.array_equal(np.asarray(g), np.asarray(w)))
            for g, w in zip(got, want))
    q = rng.normal(size=(8, DIM)).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    got = sops.topk_scores(jnp.asarray(q), index.embeddings, KPRIME,
                           use_pallas=True)
    want = sops.topk_scores(jnp.asarray(q), index.embeddings, KPRIME,
                            use_pallas=False)
    out["score_topk[b8]"] = bool(np.array_equal(np.asarray(got.indices),
                                                np.asarray(want.indices)))
    out["score_topk_max_abs_diff"] = float(np.max(np.abs(
        np.asarray(got.values) - np.asarray(want.values))))
    log(kernels=out)
    check(all(v for k, v in out.items() if k != "score_topk_max_abs_diff"),
          "a kernel disagrees with its XLA reference on the chip")


def lower_scoring_step(index, kprime: int):
    """The dense scoring step (`rlwe._cached_scores`) lowered at the served
    batch and k' over the index's pool, on the Pallas kernels."""
    import jax
    import jax.numpy as jnp

    from repro.crypto import rlwe

    params = rlwe.RlweParams()
    cache = index.candidate_cache(params)
    cpt = cache.cands_per_ct
    pad = -(-kprime // cpt) * cpt - kprime
    c0 = jax.ShapeDtypeStruct((MAX_BATCH, cache.num_chunks,
                               params.num_primes, params.n_poly), jnp.int32)
    ids = jax.ShapeDtypeStruct((MAX_BATCH, kprime), jnp.int32)
    return rlwe._cached_scores.lower(
        c0, c0, cache.polys, ids, cache.twiddles, ctxs=params.ctxs, cpt=cpt,
        pad=pad, use_pallas=True)


def lowering_phase(index, kprime: int) -> dict:
    """Kernels carried by the lowered top-k' and scoring steps."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.kernels.scoretopk import ops as sops

    score_text = lower_scoring_step(index, kprime).as_text()
    q = jax.ShapeDtypeStruct((MAX_BATCH, DIM), jnp.float32)
    topk_text = jax.jit(lambda q, e: sops.topk_scores(q, e, kprime)).lower(
        q, index.embeddings).as_text()
    found = sorted(set(re.findall(r'kernel_name = "([^"]+)"',
                                  score_text + topk_text)))
    want = ["ntt_fwd", "rerank_fused_intt", "score_topk"]
    log(lowered_kernels={k: k in found for k in want},
        tpu_custom_calls=(score_text + topk_text).count("tpu_custom_call"))
    check(all(k in found for k in want),
          f"lowered steps carry {found}, expected {want}")


def pool_layout_phase(index) -> None:
    """No instruction of the compiled dense scoring step but a parameter
    has a result with ``DOCS`` rows: the gather reads the pool in place."""
    import re

    text = lower_scoring_step(index, KPRIME).compile().as_text()
    pool_sized = re.findall(
        rf"^\s*(?:ROOT )?%(\S+) = (\w+\[{DOCS},[^\]]*\]\S*) (\S+)\(",
        text, re.M)
    log(pool_sized_instructions=[
        {"name": name, "result": result, "op": op}
        for name, result, op in pool_sized])
    ops = {op for _, _, op in pool_sized}
    check(ops == {"parameter"},
          f"the compiled dense scoring step makes pool-sized arrays: {ops}")


def log_memory(dev, after: str) -> None:
    stats = dev.memory_stats() or {}
    log(memory={"after": after, "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")})


def serve_phase(seed: int, dev) -> None:
    import numpy as np

    from repro.crypto import rlwe
    from repro.launch import serve as launch

    args = launch.parse_args([
        "--n-docs", str(DOCS), "--dim", str(DIM), "--k", str(K),
        "--radius", str(RADIUS), "--requests", str(REQUESTS),
        "--tenants", str(TENANTS), "--max-batch", str(MAX_BATCH),
        "--seed", str(seed)])
    t0 = time.perf_counter()
    index = launch.build_index(args)
    t1 = time.perf_counter()
    log_memory(dev, "corpus")
    index.candidate_cache(rlwe.RlweParams())     # dense NTT-domain pool
    t2 = time.perf_counter()
    log(setup={"corpus_s": t1 - t0, "candidate_cache_s": t2 - t1,
               "pool_bytes": index.candidate_cache(rlwe.RlweParams()).nbytes})
    log_memory(dev, "candidate_cache")

    kernels_phase(index, np.random.default_rng(seed))
    log_memory(dev, "kernels")

    runs = {}
    for name, use_pallas in (("pallas_cold", None), ("pallas_warm", None),
                             ("xla", False)):
        lines = []
        t = time.perf_counter()
        report = launch.serve(args, index, use_pallas=use_pallas,
                              deterministic_seeds=True, emit=lines.append)
        wall = time.perf_counter() - t
        if name == "pallas_cold":
            for line in lines:
                print(line, flush=True)
        lat = sorted(r.latency_s for r in report.results)
        log(run=name, wall_s=wall, requests=len(report.results),
            latency_p50_s=lat[len(lat) // 2], latency_max_s=lat[-1])
        log_memory(dev, name)
        runs[name] = report
        check(len(report.results) == REQUESTS,
              f"{name}: {len(report.results)} of {REQUESTS} results")
        check(all(r.ok and not r.quarantined and r.shed_reason is None
                  for r in report.results),
              f"{name}: a request failed, was quarantined or shed")
        check("failures" not in report.summary,
              f"{name}: failures {report.summary.get('failures')}")
        emb = np.asarray(index.embeddings)
        for r in report.results:
            exact = np.argsort(-(emb @ report.queries[r.request_id]),
                               kind="stable")[:K]
            check(set(r.ids.tolist()) == set(exact.tolist()),
                  f"{name}: request {r.request_id} recall < 1.0")
        if name == "pallas_cold":
            lowering_phase(index, report.plan.kprime)
            log(plan={"kprime": report.plan.kprime,
                      "path": report.plan.path, "eps": report.plan.eps})
            check(report.plan.kprime == KPRIME,
                  f"planner gave k'={report.plan.kprime}, expected {KPRIME}")

    def key(report):
        return [(r.request_id, r.ids.tolist(), r.docs,
                 r.transcript.total_bytes) for r in report.results]
    check(key(runs["pallas_cold"]) == key(runs["pallas_warm"]),
          "warm rerun differs from the cold run")
    check(key(runs["pallas_cold"]) == key(runs["xla"]),
          "Pallas and XLA-reference engines differ in ids, docs or bytes")
    stats = dev.memory_stats() or {}
    log(parity="pallas_cold == pallas_warm == xla (ids, docs, wire bytes)",
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))
    pool_layout_phase(index)


def paillier_phase(seed: int) -> None:
    """Paillier on the chip.  The vectorized tier's float64 residue
    channels run only where doubles are exact (`repro.kernels.
    exact_float64`: the CPU).  This phase is the chip's evidence for that
    rule and checks the refusal:

      1. one batch of the bignum kernels' Montgomery multiply at a 512-bit
         key (~46 channels), on the chip, against the exact NumPy mirror of
         the object path's integers (logged: the rule's evidence);
      2. every vectorized entry point (encrypt, score, decrypt) raises
         `InexactDevice`;
      3. `--backend paillier` through `repro.launch.serve`, batched and
         sequential, ends every request in `InexactDevice` — no wrong
         score is served."""
    import jax
    import numpy as np

    from repro.crypto import paillier as pai
    from repro.crypto import paillier_vec as pvec
    from repro.kernels.bignum import ops, ref
    from repro.launch import serve as launch

    rng = np.random.default_rng(seed)
    key = pai.keygen(512, rng=rng)
    ctx = ref.for_modulus(key.pub.n_sq)
    a, b = ([ref.to_mont(ctx, pai._randbelow(ctx.modulus, rng))
             for _ in range(64)] for _ in range(2))
    am, bm = ref.to_rns(ctx, a), ref.to_rns(ctx, b)
    t = time.perf_counter()
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(ops.mont_mul)(
            am[None], bm[None], pvec._consts([ctx], batch_ndim=2)))[0]
    want = ref.from_rns(ctx, ref.mont_mul(ctx, am, bm))
    wrong = sum((g - w) % ctx.modulus != 0
                for g, w in zip(ref.from_rns(ctx, got), want))
    log(paillier_channels={"mont_mul": len(want), "wrong": wrong,
                           "s": time.perf_counter() - t})

    dim, kprime = 32, 8
    e = rng.normal(size=dim)
    e /= np.linalg.norm(e)
    cands = rng.normal(size=(kprime, dim))
    cands /= np.linalg.norm(cands, axis=-1, keepdims=True)
    enc = pai.encrypt_vector(key.pub, e, rng)
    cts = pai.encrypted_scores(key.pub, enc, cands, rng=rng)
    refused = {}
    for name, call in (
            ("encrypt", lambda: pvec.encrypt_vector(key.pub, e, rng)),
            ("score", lambda: pvec.encrypted_scores_batch(
                [key.pub], [enc], [cands])),
            ("decrypt", lambda: pvec.decrypt_scores_batch([key], [cts]))):
        try:
            call()
            refused[name] = False
        except pvec.InexactDevice:
            refused[name] = True
    log(paillier_tier_refused=refused)
    check(all(refused.values()), "vectorized Paillier served on the chip")

    argv = ["--backend", "paillier", "--n-docs", "2000", "--dim", str(dim),
            "--k", "3", "--requests", "4", "--tenants", "2",
            "--max-batch", "4", "--seed", str(seed)]
    for name, extra in (("batched", []), ("sequential", ["--no-batch"])):
        args = launch.parse_args(argv + extra)
        t = time.perf_counter()
        report = launch.serve(args, deterministic_seeds=True,
                              emit=lambda _line: None)
        errors = sorted({(r.error or "").split("(")[0]
                         for r in report.results if not r.ok})
        log(paillier_serve={"run": name, "s": time.perf_counter() - t,
                            "ok": sum(r.ok for r in report.results),
                            "errors": report.errors,
                            "error_kinds": errors})
        check(report.errors == len(report.results) and all(
            "InexactDevice" in (r.error or "") for r in report.results),
              f"paillier {name}: served on the chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paillier", action="store_true",
                    help="check Paillier's float64 channels on the chip "
                         "and their refusal (this phase only)")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.launch import serve as launch

    log(device={"platform": dev.platform, "kind": dev.device_kind,
                "count": len(devices)},
        compile_cache=launch.enable_compile_cache())
    try:
        if args.paillier:
            paillier_phase(args.seed)
        else:
            serve_phase(args.seed, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(ok=True, device={"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
