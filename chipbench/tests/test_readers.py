"""The readers of the engine's spans (``stage_ms.score``,
``stage_ms.decrypt``, ``queue_wait_p50_ms``, ``engine_busy_share``) on
hand-made spans: lane weighting, the window's edges, and no value where
no span exists."""

import pytest

from chipbench import loadgen, spec
from repro.obs import Span

T0, SECONDS = 100.0, 10.0


def read(name, spans):
    run = {"spans": spans, "log": loadgen.Log.empty(0, T0, SECONDS)}
    return spec.load_reader(name)(run)


def span(name, t_start, duration_s, **attrs):
    return Span(name=name, track="engine", t_start=t_start,
                duration_s=duration_s, attrs=attrs)


def batches():
    """Two batches, of 1 and 3 lanes."""
    return [span("score", 101.0, 0.010, lanes=1),
            span("decrypt", 101.01, 0.004, lanes=1),
            span("score", 102.0, 0.050, lanes=3),
            span("decrypt", 102.05, 0.008, lanes=3),
            span("encrypt", 101.5, 0.5)]


def test_stage_readers_weight_each_batch_by_its_lanes():
    spans = batches()
    # (10 + 50) ms over 4 lanes, not the mean of the batches' 10 and 16.7
    assert read("stage_ms.score", spans) == pytest.approx(15.0)
    assert read("stage_ms.decrypt", spans) == pytest.approx(3.0)
    assert read("stage_ms.score", spans) + read(
        "stage_ms.decrypt", spans) == pytest.approx(
        read("stage_ms.rerank", spans))


def test_decrypt_is_counted_over_the_lanes_score_served():
    # a lane dropped at scoring leaves decrypt with fewer lanes; the
    # stages still sum to stage_ms.rerank
    spans = [span("score", 101.0, 0.030, lanes=3),
             span("decrypt", 101.03, 0.006, lanes=2)]
    assert read("stage_ms.decrypt", spans) == pytest.approx(2.0)
    assert read("stage_ms.score", spans) + read(
        "stage_ms.decrypt", spans) == pytest.approx(
        read("stage_ms.rerank", spans))


def test_queue_wait_median_of_requests_enqueued_in_the_window():
    spans = [span("queue_wait", 99.9, 5.0),          # enqueued before
             span("queue_wait", 100.0, 0.010),
             span("queue_wait", 104.0, 0.030),
             span("queue_wait", 109.99, 0.020),      # dispatched after
             span("queue_wait", 110.0, 9.0),         # enqueued after
             span("dispatch", 104.03, 0.2)]
    assert read("queue_wait_p50_ms", spans) == pytest.approx(20.0)


def test_engine_busy_share_clips_dispatches_at_the_window_edges():
    spans = [span("dispatch", 99.0, 2.0),      # 1 s inside
             span("dispatch", 104.0, 0.5),
             span("dispatch", 109.5, 3.0),     # 0.5 s inside
             span("dispatch", 111.0, 1.0),     # after the window
             span("score", 104.1, 0.2, lanes=1)]
    assert read("engine_busy_share", spans) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["stage_ms.score", "stage_ms.decrypt",
                                  "queue_wait_p50_ms", "engine_busy_share"])
def test_no_span_no_value(name):
    assert read(name, []) is None
    assert read(name, [span("encrypt", 101.0, 0.01)]) is None


def test_decrypt_without_its_spans_is_no_value():
    assert read("stage_ms.decrypt", [span("score", 101.0, 0.01,
                                          lanes=2)]) is None
