"""The plain reference of private retrieval, and the comparison that decides
a run's ``correct``.

The reference imports nothing of the program: it is NumPy over the
benchmark's own corpus, documents and queries.  For each request the
program served it computes what a plaintext service with the configuration's
semantics returns:

- top-k' candidates of the perturbed query (the message the cloud
  receives) by exact inner product over the whole corpus, in float64;
- each candidate's score as the configuration's fixed-point arithmetic
  defines it: round(q * 2^sq) . round(c * 2^sc) / 2^(sq + sc), exactly, in
  int64 (what RLWE decryption must return);
- the top k of those scores (stable: ties keep candidate order), and the
  documents of those ids.

The numbers compared, each against its limit in `LIMITS`:

- ``noise_radius_dev``: over every request, the widest relative gap
  between the distance from its true query to the perturbed query the
  cloud received and the mean n / eps of the configuration's (n, eps)-
  DistanceDP radius, Gamma(n, 1/eps): |r eps / n - 1|.  It reads about
  3.5/sqrt(n) on sound noise, 1 where the query went out unperturbed and
  0.5 at twice the budget;
- ``scan_gap``: over a seeded sample of requests, the widest gap by which
  the least reference score among the program's k' candidates lies below
  the reference's k'-th best score (0 for an exact top-k'; a candidate list
  of the wrong length, with repeats or out of range counts as 1);
- ``score_err_lsb``: the largest difference between a decrypted score and
  the reference score, in units of 2^-(sq + sc), over every request;
- ``topk_mismatch``: requests whose k ids are not the reference's top k;
- ``doc_mismatch``: requests whose documents are not those of their ids;
- ``failed``: requests due in the window that did not come back ok;
- ``unrecorded``: ok requests the recorder saw no scan or scores for.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

# scan_gap: sound runs on one v5e read at most 1.2e-16 (float64 round-off:
# the f32 HIGHEST scan picks the exact top-k'), the bfloat16 control at
# least 1.28e-4 (PERF.md section 2).  The other numbers are exact.
# noise_radius_dev: Gamma(768, 1)/768 has a standard deviation of 0.036;
# the limit is 6.9 of them, half of what twice the budget reads.
LIMITS: Dict[str, float] = {
    "noise_radius_dev": 0.25,
    "scan_gap": 1e-5,
    "score_err_lsb": 0,
    "topk_mismatch": 0,
    "doc_mismatch": 0,
    "failed": 0,
    "unrecorded": 0,
}
SAMPLE = 96            # requests whose scan is checked against the corpus
ROW_BLOCK = 1 << 15    # corpus rows per float64 block


def kth_best(emb: np.ndarray, queries: np.ndarray, k: int) -> np.ndarray:
    """The k-th largest exact (float64) inner product of each query over
    all corpus rows, scanned in row blocks."""
    q = np.asarray(queries, np.float64)
    best = np.full((0, len(q)), -np.inf)
    for lo in range(0, emb.shape[0], ROW_BLOCK):
        s = emb[lo:lo + ROW_BLOCK].astype(np.float64) @ q.T      # (rows, B)
        both = np.concatenate([best, s])
        take = min(k, len(both))
        best = np.partition(both, len(both) - take, axis=0)[-take:]
    return best.min(axis=0)


def scan_gaps(emb: np.ndarray, perturbed: np.ndarray,
              cands: Sequence[np.ndarray], kprime: int) -> np.ndarray:
    """Per request: reference k'-th best minus the least reference score
    among the given candidates (1.0 for a malformed candidate list)."""
    kth = kth_best(emb, perturbed, kprime)
    out = np.empty(len(cands))
    for i, c in enumerate(cands):
        c = np.asarray(c)
        if (len(c) != kprime or len(np.unique(c)) != kprime
                or c.min() < 0 or c.max() >= emb.shape[0]):
            out[i] = 1.0
            continue
        got = emb[c].astype(np.float64) @ np.asarray(perturbed[i],
                                                     np.float64)
        out[i] = kth[i] - got.min()
    return out


def noise_radius_dev(queries: np.ndarray, records: dict,
                     dp_eps: float) -> float:
    """The widest |r eps / n - 1| over the requests of ``records`` (request
    index -> (perturbed, ...)), r the float64 distance from the true query
    to the perturbed one (1.0 where nothing was recorded)."""
    if not records:
        return 1.0
    idx = sorted(records)
    pert = np.stack([records[i][0] for i in idx]).astype(np.float64)
    true = np.asarray(queries, np.float64)[idx]
    if pert.shape != true.shape:
        return 1.0
    r = np.linalg.norm(pert - true, axis=1)
    return float(np.max(np.abs(r * dp_eps / true.shape[1] - 1.0)))


def fixed_point_scores(query: np.ndarray, rows: np.ndarray, sq: int,
                       sc: int) -> np.ndarray:
    """round(q 2^sq) . round(c 2^sc) for each candidate row, in int64."""
    qi = np.rint(np.asarray(query, np.float64) * (1 << sq)).astype(np.int64)
    ci = np.rint(np.asarray(rows, np.float64) * (1 << sc)).astype(np.int64)
    return ci @ qi


def compare(*, emb: np.ndarray, docs: List[bytes], queries: np.ndarray,
            attempted: np.ndarray, ok: np.ndarray, results: dict,
            records: dict, k: int, kprime: int, sq: int, sc: int,
            dp_eps: float, rng: np.random.Generator) -> Dict[str, float]:
    """The numbers compared for one run.  ``queries[i]`` is request i's
    true query; ``results``/``records`` map request index to the program's
    `ServeResult` and to the recorder's (perturbed, candidates, scores)."""
    served = [i for i in attempted if ok[i]]
    failed = len(attempted) - len(served)
    checked = [i for i in served if i in records]
    score_err = 0
    topk_bad = doc_bad = 0
    for i in checked:
        _pert, cand, scores = records[i]
        cand = np.asarray(cand)
        want = fixed_point_scores(queries[i], emb[cand], sq, sc)
        got = np.rint(np.asarray(scores, np.float64) * float(1 << (sq + sc)))
        if len(got) != len(want):
            score_err = max(score_err, 1 << (sq + sc))
        else:
            score_err = max(score_err, int(np.max(np.abs(
                got.astype(np.int64) - want))))
        want_ids = cand[np.argsort(-want, kind="stable")[:k]]
        res = results[i]
        if not np.array_equal(np.asarray(res.ids), want_ids):
            topk_bad += 1
        if list(res.docs) != [docs[int(j)] for j in np.asarray(res.ids)]:
            doc_bad += 1
    gap = 0.0
    if checked:
        pick = rng.choice(len(checked), size=min(SAMPLE, len(checked)),
                          replace=False)
        sample = [checked[j] for j in sorted(pick)]
        gap = float(np.max(scan_gaps(
            emb, np.stack([records[i][0] for i in sample]),
            [records[i][1] for i in sample], kprime)))
    return {"noise_radius_dev": noise_radius_dev(
                queries, {i: records[i] for i in checked}, dp_eps),
            "scan_gap": gap, "score_err_lsb": float(score_err),
            "topk_mismatch": float(topk_bad), "doc_mismatch": float(doc_bad),
            "failed": float(failed),
            "unrecorded": float(len(served) - len(checked))}


def verdict(numbers: Dict[str, float]) -> bool:
    return all(numbers[name] <= limit for name, limit in LIMITS.items())
