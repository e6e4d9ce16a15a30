"""repro.obs: span schema redaction, ring bounding, stage histograms,
Chrome-trace export, profiler annotations.  The engine-integration side (stage coverage over a
real served stream, admitter-span parenting/overlap) lives in
tests/test_serve.py next to the engine tests."""

import glob
import json
import math
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import obs
from repro.obs.trace import _MAX_STR


def _clock(seq):
    """Deterministic fake clock: pops successive times from a list."""
    it = iter(seq)
    return lambda: next(it)


# -- redaction contract -----------------------------------------------------

def test_redaction_rejects_unknown_keys():
    tracer = obs.Tracer()
    # the exact attack the schema exists to stop: logging doc ids
    with pytest.raises(ValueError, match="ALLOWED_ATTR_KEYS"):
        tracer.event("gather", doc_ids=17)
    with pytest.raises(ValueError, match="ALLOWED_ATTR_KEYS"):
        tracer.event("perturb", embedding=1.0)
    assert tracer.spans() == []        # nothing was recorded


def test_redaction_rejects_non_scalar_values():
    tracer = obs.Tracer()
    for payload in (np.zeros(4),           # an embedding
                    [0.1, 0.9],            # a score vector
                    b"plaintext",          # document bytes
                    {"id": 3},             # structured payload
                    (1, 2)):
        with pytest.raises(TypeError, match="non-scalar"):
            tracer.event("stage", count=payload)
    with pytest.raises(ValueError, match="chars"):
        tracer.event("stage", reason="x" * (_MAX_STR + 1))
    assert tracer.spans() == []


def test_redaction_converts_numpy_scalars():
    out = obs.validate_attrs({"count": np.int64(3),
                              "bytes": np.float32(1.5),
                              "ok": True, "tenant": "alice"})
    assert out == {"count": 3, "bytes": 1.5, "ok": True, "tenant": "alice"}
    assert type(out["count"]) is int and type(out["bytes"]) is float


def test_span_failure_records_error_class_name_only():
    tracer = obs.Tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("score", lanes=4):
            raise RuntimeError("secret query payload in the message")
    (span,) = tracer.spans()
    assert span.attrs["error_type"] == "RuntimeError"
    # the exception *message* must never reach the span
    assert "secret" not in json.dumps(
        [dict(s.attrs) for s in tracer.spans()])


# -- tracer mechanics -------------------------------------------------------

def test_ring_buffer_bounded_histograms_complete():
    tracer = obs.Tracer(capacity=4, clock=_clock(
        [float(t) for i in range(10) for t in (i, i + 0.5)]))
    for i in range(10):
        with tracer.span("stage", lanes=i):
            pass
    spans = tracer.spans()
    assert len(spans) == 4                    # ring bound
    assert tracer.dropped == 6
    assert spans[-1].attrs["lanes"] == 9      # newest kept
    # the histogram saw every span, wrapped or not
    assert tracer.stage_summary()["stage"]["count"] == 10
    snap = tracer.snapshot()
    assert snap["spans"] == 4 and snap["dropped"] == 6
    tracer.clear()
    assert tracer.spans() == [] and tracer.stage_summary() == {}
    with pytest.raises(ValueError, match="capacity"):
        obs.Tracer(capacity=0)


def test_record_explicit_interval_and_event():
    tracer = obs.Tracer(clock=_clock([5.0]))
    span = tracer.record("queue_wait", 1.0, 3.5, request_id=7, batch_id=2,
                         tenant="bob")
    assert span.duration_s == 2.5 and span.t_end == 3.5
    assert span.request_id == 7 and span.batch_id == 2
    evt = tracer.event("refill", requests=3)
    assert evt.duration_s == 0.0 and evt.t_start == 5.0
    # events don't pollute the stage histograms with zero durations
    assert "refill" not in tracer.stage_summary()
    assert tracer.stage_summary()["queue_wait"]["count"] == 1


def test_null_tracer_is_inert():
    nt = obs.NULL_TRACER
    assert not nt.enabled
    with nt.span("stage", lanes=8):
        pass
    assert nt.record("x", 0, 1) is None and nt.event("x") is None
    assert nt.spans() == [] and nt.stage_summary() == {}
    assert nt.snapshot()["spans"] == 0
    # even bad attrs are ignored when disabled — no validation cost
    with nt.span("stage", embedding=np.zeros(3)):
        pass


# -- profiler annotations ---------------------------------------------------

@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The ``repro/`` host events of one CPU profiler session in which a
    tracer and the NULL tracer each time a few spans; returns
    ({event name: [stats dict, ...]}, the tracer)."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    tracer = obs.Tracer(common={"replica": 1})
    jax.profiler.start_trace(log_dir)
    try:
        with tracer.span("x", batch_id=3, lanes=2, backend="rlwe"):
            pass
        with tracer.span("finish", request_id=7, batch_id=3, lane=0):
            pass
        with pytest.raises(ValueError):
            with tracer.span("y", doc_ids=17):
                pass
        with pytest.raises(RuntimeError):
            with tracer.span("boom", batch_id=4):
                raise RuntimeError("secret query payload")
        with obs.NULL_TRACER.span("null", batch_id=5, lanes=1):
            pass
        tracer.record("queue_wait", 0.0, 1.0, batch_id=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.ANNOTATION_PREFIX):
                    events.setdefault(ev.name, []).append(dict(ev.stats))
    return events, tracer


def test_span_annotates_the_profiler_host_timeline(profiled):
    events, tracer = profiled
    (x,) = events["repro/x"]
    assert x == {"batch_id": 3, "lanes": 2, "backend": "rlwe",
                 "replica": 1}
    (fin,) = events["repro/finish"]
    assert fin["request_id"] == 7 and fin["batch_id"] == 3
    # the redaction contract covers the profile as it covers the ring
    allowed = obs.ALLOWED_ATTR_KEYS | {"batch_id", "request_id"}
    for stats in (s for evs in events.values() for s in evs):
        assert set(stats) <= allowed
    # a failing block still closes its annotation and records its span
    assert len(events["repro/boom"]) == 1
    assert [s.name for s in tracer.spans()] == [
        "x", "finish", "boom", "queue_wait"]


def test_annotations_only_from_enabled_span(profiled):
    """NULL_TRACER opens nothing, a span whose attrs break the schema
    opens nothing, and `record` (an interval stamped afterwards) opens
    nothing."""
    events, _ = profiled
    assert set(events) == {"repro/x", "repro/finish", "repro/boom"}


# -- histograms -------------------------------------------------------------

def test_histogram_percentiles_and_merge():
    h = obs.StageHistogram()
    assert math.isnan(h.percentile(50))
    assert h.summary() == {"count": 0}
    for d in (1e-6, 2e-6, 4e-6, 1e-3, 1.0):
        h.record(d)
    s = h.summary()
    assert s["count"] == 5
    assert s["min_s"] == 1e-6 and s["max_s"] == 1.0
    # bucket upper-edge estimate: median sample 4us sits exactly on an edge
    assert h.percentile(50) == pytest.approx(4e-6)
    # p100 falls in the bucket holding 1.0s; upper edge is 2^20us
    assert 1.0 <= h.percentile(100) <= 2.1
    h2 = obs.StageHistogram()
    h2.record(10.0)
    h.merge(h2)
    assert h.count == 6 and h.max_s == 10.0
    # durations beyond the last edge land in the overflow bucket and
    # report the exact max
    h3 = obs.StageHistogram()
    h3.record(500.0)
    assert h3.percentile(99) == 500.0


# -- chrome-trace export ----------------------------------------------------

def test_chrome_trace_roundtrip(tmp_path):
    tracer = obs.Tracer(clock=_clock([10.0, 10.5, 10.1, 10.2]))
    with tracer.span("dispatch", batch_id=0, batch_size=2):
        pass                                   # 10.0 -> 10.5
    tracer.record("cache_admit", 10.1, 10.3, track="admitter",
                  batch_id=0, shard=3)
    path = tmp_path / "trace.json"
    n = obs.write_chrome_trace(str(path), tracer.spans(),
                               stage_summary=tracer.stage_summary())
    assert n == 2
    doc = obs.load_chrome_trace(str(path))
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    durs = [e for e in events if e["ph"] == "X"]
    assert {m["args"]["name"] for m in meta} == {"engine", "admitter"}
    by_name = {e["name"]: e for e in durs}
    # ts normalized to the earliest span, microseconds
    assert by_name["dispatch"]["ts"] == 0.0
    assert by_name["dispatch"]["dur"] == pytest.approx(5e5)
    assert by_name["cache_admit"]["ts"] == pytest.approx(1e5)
    assert by_name["cache_admit"]["args"]["shard"] == 3
    assert by_name["cache_admit"]["args"]["batch_id"] == 0
    # distinct tracks get distinct tids; "engine" is row 1
    assert by_name["dispatch"]["tid"] != by_name["cache_admit"]["tid"]
    assert by_name["dispatch"]["tid"] == 1
    assert doc["metadata"]["stage_summary"]["dispatch"]["count"] == 1
    assert obs.chrome_trace_events([]) == []
