"""A deployment is its files: a configuration's ``corpus`` and ``cache``
blocks and a mix's topic, hot-set and burst keys reach the program through
the harness as it stands, and the harness refuses what they cannot mean."""

import json
import time

import numpy as np
import pytest

from chipbench import corpus, loadgen, reference, run, serving
from chipbench.spec import SpecError
from repro.core import planner

CONFIG = "chipbench/configs/t4-768-dense-100k.json"
SEED = 2**40 + 23
# a query's exact k' neighbours in its own topic, at least (PERF.md
# section 4: spread 0.02 per coordinate at 768 dims, query jitter 0.15)
IN_TOPIC_SHARE = 0.99


def tiny_sharded():
    """3000 docs of width 768 in 8 topics of 375, k' = 32, RLWE at
    N = 1024 (24 KiB a doc), shards of 375 docs and a device budget of two
    of the eight."""
    cfg = json.loads((run.ROOT / CONFIG).read_text())
    cfg.update(name="tiny-sharded", n_docs=3000, kprime=32, doc_bytes=64,
               max_batch=2, candidates="sharded_cache",
               dp_eps=planner.plan(n=768, N=3000, k=5, kprime=32).eps,
               corpus={"topics": 8, "spread": 0.02},
               cache={"shard_docs": 375,
                      "max_resident_bytes": 2 * 375 * 24 * 1024})
    cfg["crypto"].update(n_poly=1024, chunk=512)
    return cfg


BURSTS = {"burst_factor": 2.0, "burst_period_s": 1.0, "burst_duty": 0.25}
MIXES = {
    "skewed": dict(BURSTS, topic_zipf=1.2, hot_shift_s=1.0),
    "uniform_topics": dict(BURSTS, topic_zipf=0.0),
}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("deployment")
    d = root / "chipbench"
    for sub in ("configs", "traffic"):
        (d / sub).mkdir(parents=True)
    (d / "configs" / "tiny-sharded.json").write_text(
        json.dumps(tiny_sharded()))
    base = {"loop": "open", "rate_rps": 8.0, "tenants": 3,
            "tenant_zipf": 0.99, "query_jitter": 0.15}
    for name, keys in MIXES.items():
        (d / "traffic" / f"{name}.json").write_text(
            json.dumps(dict(base, **keys)))
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-sharded", "source": "test",
                         "reduced": [], "why": "t",
                         "file": "chipbench/configs/tiny-sharded.json"}]
    bench["workloads"] = [
        {"name": f"tiny.{m}", "config": "tiny-sharded", "traffic": m,
         "chips": 1, "why": "t"} for m in MIXES]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sharded_cache_on_topics_is_correct(tree, mix, monkeypatch):
    """The skewed, bursty mix and its uniform-topic control both run
    correct through the sharded cache, which serves the window."""
    stats = []
    real = serving.System.cache_stats

    def cache_stats(self):
        stats.append(real(self))
        return stats[-1]

    monkeypatch.setattr(serving.System, "cache_stats", cache_stats)
    out = run.run_cell(f"tiny.{mix}", SEED, 3.0, False, root=tree,
                       require_tpu=False, t_start=time.monotonic())
    assert out["correct"], out["checks"]
    assert out["attempted"] == 24 and out["failed"] == 0
    before, after = stats
    assert after["num_shards"] == 8
    # shard lookups (one per shard a batch's gather touched) in the window
    assert (after["hits"] + after["misses"]
            > before["hits"] + before["misses"])


def queries_in_topics(n, dim, block, k, seed):
    rng = np.random.default_rng(seed)
    emb, bounds = corpus.topic_rows(rng, n, dim, block)
    topic = rng.integers(0, len(bounds) - 1, 64)
    q = corpus.queries_near(rng, emb, rng.integers(bounds[topic],
                                                   bounds[topic + 1]), 0.15)
    top = np.argpartition(-(emb @ q.T), k, axis=0)[:k]
    return np.searchsorted(bounds, top, side="right") - 1 == topic


def test_exact_neighbours_lie_in_the_querys_topic():
    """At k' = 160 over 16384 docs of width 768 in 8 topics, spread 0.02
    and query jitter 0.15, the query's exact k' neighbours lie in its own
    topic: at least the share PERF.md states."""
    inside = queries_in_topics(16384, 768, {"topics": 8, "spread": 0.02},
                               160, seed=5)
    assert inside.mean() >= IN_TOPIC_SHARE


def test_topics_are_contiguous_unit_rows_of_zipf_sizes():
    emb, bounds = corpus.topic_rows(np.random.default_rng(1), 1000, 256,
                                    {"topics": 4, "spread": 0.03,
                                     "size_zipf": 1.0})
    assert emb.dtype == np.float32
    assert np.allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-5)
    sizes = np.diff(bounds)
    assert bounds[0] == 0 and bounds[-1] == 1000
    assert list(sizes) == sorted(sizes, reverse=True)
    assert sizes[0] / sizes[1] == pytest.approx(2.0, rel=0.02)
    # rows of one topic lie close together, rows of two apart
    same = emb[bounds[0]:bounds[1]] @ emb[bounds[0]:bounds[1]].T
    apart = emb[bounds[0]:bounds[1]] @ emb[bounds[1]:bounds[2]].T
    assert same.mean() > 0.5 > 0.2 > abs(apart.mean())


def open_mix(**keys):
    return dict({"loop": "open", "rate_rps": 50.0, "tenants": 4,
                 "tenant_zipf": 0.99}, **keys)


def test_bursts_keep_the_count_and_concentrate_arrivals():
    seconds, period, duty, factor = 40.0, 4.0, 0.25, 3.0
    steady = loadgen.make_schedule(open_mix(), seconds, 1000,
                                   np.random.default_rng(3))
    bursty = loadgen.make_schedule(
        open_mix(burst_factor=factor, burst_period_s=period,
                 burst_duty=duty), seconds, 1000, np.random.default_rng(3))
    assert len(bursty) == len(steady) == 2000
    # the draws before the bursts are today's: same tenants and rows
    assert np.array_equal(bursty.tenant, steady.tenant)
    assert np.array_equal(bursty.row, steady.row)
    assert np.all(np.diff(bursty.due_s) >= 0) and bursty.due_s[-1] < seconds
    inside = np.mod(bursty.due_s, period) < duty * period
    mean_rps = len(bursty) / seconds
    burst_rps = inside.sum() / (duty * seconds)
    # the stratified arrivals fluctuate about their rate by a few %
    assert burst_rps / mean_rps == pytest.approx(factor, rel=0.05)
    low = (1 - duty * factor) / (1 - duty)
    rest_rps = (~inside).sum() / ((1 - duty) * seconds)
    assert rest_rps / mean_rps == pytest.approx(low, rel=0.1)


def test_hot_shifts_change_the_top_topic_at_each_shift():
    topics = np.arange(0, 3001, 100)             # 30 topics of 100 rows
    mix = open_mix(topic_zipf=1.5, hot_shift_s=5.0)
    s = loadgen.make_schedule(mix, 40.0, 3000, np.random.default_rng(9),
                              topics)
    topic = s.row // 100
    epoch = (s.due_s // 5.0).astype(int)
    tops = [np.bincount(topic[epoch == e], minlength=30).argmax()
            for e in range(8)]
    assert all(a != b for a, b in zip(tops, tops[1:]))
    # Zipf(1.5) over 30 ranks: the top topic takes ~ 1/zeta share
    counts = np.bincount(topic[epoch == 0], minlength=30)
    assert counts.max() / counts.sum() > 0.3
    # a closed loop shifts by request index at its nominal rate
    closed = dict(mix, loop="closed", pool=1000, nominal_rps=20.0)
    c = loadgen.make_schedule(closed, 40.0, 3000, np.random.default_rng(9),
                              topics)
    first = np.bincount(c.row[:100] // 100, minlength=30).argmax()
    second = np.bincount(c.row[100:200] // 100, minlength=30).argmax()
    assert first != second


def test_uniform_topic_control_spreads_over_topics():
    topics = np.arange(0, 3001, 100)
    s = loadgen.make_schedule(open_mix(topic_zipf=0.0), 40.0, 3000,
                              np.random.default_rng(2), topics)
    counts = np.bincount(s.row // 100, minlength=30)
    assert counts.max() - counts.min() <= 1      # fixed counts per rank


@pytest.mark.parametrize("block, what", [
    ({"shard_docs": 375, "resident_mb": 1}, "not fields"),
    ({"async_admission": False}, "async_admission"),
    ({"pin_on_access": False}, "pin_on_access"),
    ({"admit_threshold": 0}, "admit_threshold"),
])
def test_cache_block_refused(block, what):
    cfg = dict(tiny_sharded(), cache=block)
    with pytest.raises(SpecError, match=what):
        serving.engine_config(cfg)


@pytest.mark.parametrize("candidates", ["dense_cache", "pack_per_request"])
def test_cache_block_without_the_sharded_cache_refused(candidates):
    with pytest.raises(SpecError, match="cache block"):
        serving.engine_config(dict(tiny_sharded(), candidates=candidates))


def test_sharded_cache_config_by_field_name():
    ecfg = serving.engine_config(tiny_sharded())
    assert ecfg.use_candidate_cache
    assert ecfg.cache_config.shard_docs == 375
    assert ecfg.cache_config.max_resident_bytes == 2 * 375 * 24 * 1024
    assert ecfg.cache_config.async_admission and not ecfg.sequential


@pytest.mark.parametrize("keys", [{"topic_zipf": 0.5},
                                  {"topic_zipf": 0.5, "hot_shift_s": 5.0}])
def test_topic_keys_without_topics_refused(keys):
    with pytest.raises(SpecError, match="topics"):
        loadgen.make_schedule(open_mix(**keys), 10.0, 1000,
                              np.random.default_rng(0))


@pytest.mark.parametrize("mix", [
    dict(loop="closed", pool=100, tenants=2, tenant_zipf=1.0, **BURSTS),
    open_mix(burst_factor=2.0, burst_period_s=1.0),
    open_mix(burst_factor=5.0, burst_period_s=1.0, burst_duty=0.25),
    open_mix(hot_shift_s=5.0),
    dict(loop="closed", pool=100, tenants=2, tenant_zipf=1.0,
         topic_zipf=1.0, hot_shift_s=5.0),
])
def test_mix_keys_that_do_not_fit_refused(mix):
    with pytest.raises(SpecError):
        loadgen.check_mix(mix, has_topics=True)


@pytest.mark.parametrize("block", [{"topics": 8}, {"topics": 0,
                                                   "spread": 0.1},
                                   {"topics": 4, "spread": 0.1, "zipf": 1},
                                   {"topics": 40, "spread": 0.1,
                                    "size_zipf": 3.0}])
def test_corpus_block_refused(block):
    with pytest.raises(SpecError):
        corpus.topic_rows(np.random.default_rng(0), 100, 8, block)


def test_reference_scan_is_exact_on_a_clustered_corpus():
    """Near-ties at the k'-th score are likelier on a clustered corpus; an
    exact float32 top-k' still reads float64 round-off, far under the
    limit."""
    rng = np.random.default_rng(4)
    emb, bounds = corpus.topic_rows(rng, 20000, 768,
                                    {"topics": 16, "spread": 0.02})
    rows = rng.integers(0, 20000, 48)
    q = corpus.queries_near(rng, emb, rows, 0.15)
    pert = (q + 0.03 / np.sqrt(768)
            * rng.standard_normal(q.shape)).astype(np.float32)
    f32 = np.argsort(-(emb @ pert.T), axis=0, kind="stable")[:160].T
    gaps = reference.scan_gaps(emb, pert, list(f32), 160)
    assert gaps.max() < 1e-12 < reference.LIMITS["scan_gap"]
