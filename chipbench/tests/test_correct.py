"""``correct``: a sound run passes; the bfloat16 control and each fault the
cells can have (an answer altered where it is produced, half of a batch
left out) fail it.  Drives the whole run on the CPU at a small size, with
the harness's look for a chip skipped."""

import json

import numpy as np
import pytest

from chipbench import loadgen, reference, run
from repro.core import planner

CONFIG = "chipbench/configs/t4-768-dense-100k.json"
SEED = 2**40 + 17


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A benchmark tree of one small configuration with an open and a
    closed cell: 3000 docs of the cells' width 768, k' = 32, RLWE at
    N = 1024, batches of up to 2."""
    root = tmp_path_factory.mktemp("tiny")
    d = root / "chipbench"
    for sub in ("configs", "traffic"):
        (d / sub).mkdir(parents=True)
    cfg = json.loads((run.ROOT / CONFIG).read_text())
    cfg.update(name="tiny", n_docs=3000, kprime=32, doc_bytes=64,
               max_batch=2, dp_eps=planner.plan(n=768, N=3000, k=5,
                                                kprime=32).eps)
    cfg["crypto"].update(n_poly=1024, chunk=512)
    (d / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = {"tenants": 3, "tenant_zipf": 0.99, "query_jitter": 0.15}
    (d / "traffic" / "open.json").write_text(json.dumps(
        dict(mix, loop="open", rate_rps=8.0)))
    (d / "traffic" / "closed.json").write_text(json.dumps(
        dict(mix, loop="closed", clients=4, pool=20000)))
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "t"}]
    bench["workloads"] = [
        {"name": f"tiny.{m}", "config": "tiny", "traffic": m, "chips": 1,
         "why": "t"} for m in ("open", "closed")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def one_run(root, cell, seconds=2.0):
    import time

    return run.run_cell(cell, SEED, seconds, False, root=root,
                        require_tpu=False, t_start=time.monotonic())


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_a_sound_run_is_correct(tiny, cell):
    out = one_run(tiny, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(reference.LIMITS)


def test_candidate_altered_where_produced(tiny, monkeypatch):
    from repro.serve import batching

    real = batching.topk_batch

    def topk(index, perturbed, kprime, **kw):
        res = real(index, perturbed, kprime, **kw)
        idx = np.array(res.indices)
        idx[:, -1] = (idx[:, -1] + index.num_rows // 2) % index.num_rows
        return res._replace(indices=idx)

    monkeypatch.setattr(batching, "topk_batch", topk)
    out = one_run(tiny, "tiny.closed")
    assert not out["correct"]
    assert out["checks"]["scan_gap"]["value"] > reference.LIMITS["scan_gap"]


@pytest.mark.parametrize("fault, eps_scale", [("unperturbed", 0.0),
                                              ("twice the budget", 2.0)])
def test_noise_altered_where_drawn(tiny, monkeypatch, fault, eps_scale):
    """The query leaves the user unperturbed, or with the noise of twice
    the configuration's budget (half its radius)."""
    from repro.serve import batching

    real = batching.perturb_batch

    def perturb(keys, E, epss):
        if not eps_scale:
            return np.asarray(E, np.float32)
        return real(keys, E, [eps * eps_scale for eps in epss])

    monkeypatch.setattr(batching, "perturb_batch", perturb)
    out = one_run(tiny, "tiny.closed")
    assert not out["correct"]
    assert out["checks"]["noise_radius_dev"]["value"] >= 0.49


def test_score_altered_where_decrypted(tiny, monkeypatch):
    from repro.crypto import rlwe

    real = rlwe.extract_scores

    def extract(params, d_rns, n_dim, num_cands):
        s = real(params, d_rns, n_dim, num_cands)
        s[0] += 1.0 / (params.scale_q * params.scale_c)
        return s

    monkeypatch.setattr(rlwe, "extract_scores", extract)
    out = one_run(tiny, "tiny.closed")
    assert not out["correct"]
    assert out["checks"]["score_err_lsb"]["value"] >= 1


def test_document_altered_where_fetched(tiny, monkeypatch):
    from repro.retrieval.index import FlatIndex

    real = FlatIndex.fetch_documents

    def fetch(self, ids):
        docs = real(self, ids)
        return [b"X" + d[1:] for d in docs[:1]] + docs[1:]

    monkeypatch.setattr(FlatIndex, "fetch_documents", fetch)
    out = one_run(tiny, "tiny.closed")
    assert not out["correct"]
    assert out["checks"]["doc_mismatch"]["value"] > 0


def test_half_of_each_batch_left_out(tiny, monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine._dispatch

    def dispatch(self, batch):
        return real(self, batch[: len(batch) // 2 or 1])

    monkeypatch.setattr(ServeEngine, "_dispatch", dispatch)
    monkeypatch.setattr(loadgen, "GRACE_S", 2.0)
    out = one_run(tiny, "tiny.closed")
    assert not out["correct"]
    assert out["failed"] > 0


def test_bf16_control_fails_the_scan_check():
    """The control (the reference's scan in bfloat16 in the program's
    place) reads above the scan limit, and the float32 scan below it, on
    2*10^4 docs of width 768 with queries perturbed as the cells' are."""
    from chipbench import control, corpus

    rng = np.random.default_rng(3)
    emb = corpus.unit_rows(rng, 20_000, 768)
    q = corpus.queries_near(rng, emb, rng.integers(0, 20_000, 32), 0.15)
    pert = q + 0.03 / np.sqrt(768) * rng.standard_normal(q.shape)
    pert = pert.astype(np.float32)
    kprime = 160
    ctrl = reference.scan_gaps(emb, pert,
                               list(control.bf16_candidates(emb, pert,
                                                            kprime)), kprime)
    f32 = np.argsort(-(emb @ pert.T), axis=0, kind="stable")[:kprime].T
    sound = reference.scan_gaps(emb, pert, list(f32), kprime)
    limit = reference.LIMITS["scan_gap"]
    assert sound.max() < limit < ctrl.max()
