"""The system under test, set up from a configuration and a seed.

The program receives only the benchmark's generated arrays: the corpus
through ``FlatIndex.build``, the queries and per-request noise keys through
``ServeEngine.submit``, as ``repro.launch.serve.serve`` builds them.  Set-up
does everything a deployment does before serving: the index, the candidate
pool where the configuration serves from it, one session (and key) per
tenant, and a warm-up of every batch shape 1..max_batch.

`RecordingEngine` is the engine with two taps that copy what the timed path
produced for each request (its perturbed query and top-k' candidate ids,
and the scores it decrypted) for the comparison after the window.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

import numpy as np

import jax

from repro import obs
from repro.crypto import rlwe
from repro.retrieval.index import FlatIndex
from repro.serve import EngineConfig, ServeEngine
from repro.serve.session import SessionManager

from chipbench import corpus
from chipbench.trace import ANNOTATION_PREFIX

DENSE_CACHE, PACK_PER_REQUEST = "dense_cache", "pack_per_request"


class AnnotatingTracer(obs.Tracer):
    """A `repro.obs.Tracer` that also opens a profiler annotation for each
    span, so the engine's stages sit on the device trace's clock."""

    @contextlib.contextmanager
    def span(self, name: str, **kwargs):
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
            with super().span(name, **kwargs):
                yield


class RecordingEngine(ServeEngine):
    """`ServeEngine` that keeps, per request of a clean batch, the
    perturbed query and candidate ids of its top-k' scan and the scores
    its user decrypted."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.records: Dict[int, tuple] = {}
        self.batch_sizes: List[int] = []
        self._lanes: Optional[list] = None
        self._scan: Optional[tuple] = None
        self._scores: List[np.ndarray] = []
        self._thread: Optional[int] = None

    def _dispatch(self, batch):
        self._lanes, self._scan, self._scores = list(batch), None, []
        self._thread = threading.get_ident()
        try:
            out = super()._dispatch(batch)
        finally:
            self._thread = None
        self.batch_sizes.append(len(batch))
        if self._scan is not None and len(self._scores) == len(batch):
            pert, cand = self._scan
            for lane, req in enumerate(batch):
                self.records[req.request_id] = (pert[lane], cand[lane],
                                                self._scores[lane])
        return out

    def _search_topk(self, perturbed, kprime):
        ids = super()._search_topk(perturbed, kprime)
        if (threading.get_ident() == self._thread
                and len(perturbed) == len(self._lanes)):
            self._scan = (np.array(perturbed), np.array(ids))
        return ids

    def tap_user(self, user) -> None:
        """Copy the scores ``user`` decrypts in this engine's dispatches."""
        sort = user.positions_from_scores

        def positions_from_scores(scores, num_candidates):
            if threading.get_ident() == self._thread:
                self._scores.append(np.array(scores[:num_candidates]))
            return sort(scores, num_candidates)

        user.positions_from_scores = positions_from_scores


def rlwe_params(cfg: dict) -> rlwe.RlweParams:
    c = cfg["crypto"]
    return rlwe.RlweParams(
        n_poly=c["n_poly"], num_primes=c["num_primes"], t_bits=c["t_bits"],
        scale_q_bits=c["scale_q_bits"], scale_c_bits=c["scale_c_bits"],
        eta=c["eta"], chunk=c["chunk"])


def engine_config(cfg: dict) -> EngineConfig:
    cands = cfg["candidates"]
    if cands not in (DENSE_CACHE, PACK_PER_REQUEST):
        raise ValueError(f"unknown candidates mode {cands!r}")
    return EngineConfig(max_batch=cfg["max_batch"],
                        max_wait_s=cfg["max_wait_ms"] / 1e3,
                        use_candidate_cache=cands == DENSE_CACHE)


class System:
    """One configuration's data, index, sessions and warmed engine."""

    def __init__(self, cfg: dict, seed: int, tenants: int):
        self.cfg = cfg
        self.seeds = corpus.seeds(seed)
        self.tenant_names = [f"tenant-{t}" for t in range(tenants)]
        self.phases: Dict[str, float] = {}    # set-up phase -> seconds
        self.warm_batches_s: List[float] = []  # warm-up batches of 1, 2, ...
        t = time.monotonic()
        rng = np.random.default_rng(self.seeds["corpus"])
        self.emb = corpus.unit_rows(rng, cfg["n_docs"], cfg["dim"])
        self.docs = corpus.payloads(np.random.default_rng(self.seeds["docs"]),
                                    cfg["n_docs"], cfg["doc_bytes"])
        t = self._phase("data", t)
        # the rows are unit already: the index takes them as they are, so
        # the reference scores exactly the rows the program holds
        self.index = FlatIndex.build(self.emb, documents=self.docs,
                                     normalize=False)
        self.index.embeddings.block_until_ready()
        t = self._phase("index", t)
        self.params = rlwe_params(cfg)
        self.ecfg = engine_config(cfg)
        if self.ecfg.use_candidate_cache:
            self.index.candidate_cache(self.params).polys.block_until_ready()
            t = self._phase("pool", t)
        self.sessions = SessionManager(rlwe_params=self.params)
        for i, name in enumerate(self.tenant_names):
            self.sessions.open(
                name, n=cfg["dim"], N=cfg["n_docs"], k=cfg["k"],
                backend=cfg["crypto"]["scheme"],
                seed=corpus.tenant_seed(self.seeds["tenants"], i),
                plan_kwargs={"kprime": cfg["kprime"]})
        self.plan = self.sessions.get(self.tenant_names[0]).plan
        self._phase("sessions", t)

    def _phase(self, name: str, t0: float) -> float:
        now = time.monotonic()
        self.phases[name] = now - t0
        return now

    def warm(self) -> None:
        """Run one batch of every size 1..max_batch through an engine of
        this configuration, so the window compiles nothing."""
        t = time.monotonic()
        rng = np.random.default_rng(self.seeds["warmup"])
        mb = self.ecfg.max_batch
        rows = rng.integers(0, self.cfg["n_docs"], size=mb * (mb + 1) // 2)
        queries = corpus.queries_near(rng, self.emb, rows, 0.15)
        keys = noise_keys(rng, len(rows))
        with ServeEngine(self.index, config=self.ecfg,
                         sessions=self.sessions) as eng:
            i = 0
            for bs in range(1, mb + 1):
                t_batch = time.monotonic()
                for lane in range(bs):
                    eng.submit(self.tenant_names[lane % len(self.tenant_names)],
                               queries[i], key=keys[i])
                    i += 1
                bad = [r for r in eng.drain() if not r.ok]
                if bad:
                    raise RuntimeError(f"warm-up batch of {bs} failed: "
                                       f"{bad[0].error}")
                self.warm_batches_s.append(time.monotonic() - t_batch)
        self._phase("warm", t)

    def engine(self, tracer=None) -> RecordingEngine:
        eng = RecordingEngine(self.index, config=self.ecfg,
                              sessions=self.sessions, tracer=tracer)
        for name in self.tenant_names:
            eng.tap_user(self.sessions.get(name).user)
        return eng


def noise_keys(rng: np.random.Generator, n: int) -> np.ndarray:
    """Per-request DistanceDP noise keys (raw uint32 PRNG keys)."""
    return rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
