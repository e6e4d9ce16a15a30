"""The reduction from a profiler trace to busy time, kernel time and
attributed idle gaps: on hand-made planes, and on a small trace recorded
on one TPU v5e (``data/v5e_score_topk.xplane.pb``; the source paths in
its HLO metadata were rewritten to ``/srv/bench/``)."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace as tr

FIXTURE = Path(__file__).parent / "data" / "v5e_score_topk.xplane.pb"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines])


def test_union_and_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert tr.gaps([(0, 3), (5, 8)], -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_busy_kernels_and_idle_attribution():
    host = plane("/host:CPU", [("python", [
        ev("chipbench/window", 0, 1000),
        ev("chipbench/dispatch", 100, 800),
        ev("chipbench/encrypt", 150, 200),     # inside dispatch
        ev("other", 0, 1000),                  # not an annotation
    ])])
    dev = plane("/device:TPU:0", [
        ("XLA Ops", [ev("%fusion.1 = f32[8]{0} fusion()", 50, 100),   # 50-150
                     ev("%score_topk.3 = (f32[9,8,16]{2,1,0}) custom-call()",
                        400, 100),                    # 400-500
                     ev("%fusion.2 = s32[4]{0} fusion()", 450, 100),  # -550
                     ev("%ntt_fwd = s32[4]{0} custom-call()", 1100, 50)]),
        ("XLA Modules", [ev("jit_step", 0, 1000)]),   # not an op line
    ])
    s = tr.reduce_planes([host, dev], ["score_topk", "rerank_fused_intt"])
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(250e-9)            # 50-150, 400-550
    assert s.idle_share == pytest.approx(0.75)
    assert s.kernel_s == {"score_topk": pytest.approx(100e-9)}
    assert s.kernel_calls == {"score_topk": 1}
    # gaps: 0-50 (dispatch not open yet: none), 150-400 (encrypt holds
    # 150-350, the innermost span, over dispatch's 350-400), 550-1000
    # (dispatch 550-900 against 100 ns of nothing)
    assert s.idle_gaps == [("dispatch", pytest.approx(450e-9)),
                           ("encrypt", pytest.approx(250e-9)),
                           ("none", pytest.approx(50e-9))]
    assert dict(s.top_ops) == {"fusion f32[8]": pytest.approx(100e-9),
                               "score_topk (f32[9,8,16]": pytest.approx(100e-9),
                               "fusion s32[4]": pytest.approx(100e-9)}


def test_a_trace_without_device_ops_is_refused():
    host = plane("/host:CPU", [("python", [ev("chipbench/window", 0, 10)])])
    with pytest.raises(ValueError):
        tr.reduce_planes([host])


def test_op_names():
    text = ("%score_topk.1 = (f32[977,8,161]{2,1,0:T(8,128)S(1)}, s32[977,8,"
            "161]{2,1,0}) custom-call(f32[8,768]{1,0} %queries.1)")
    assert tr.op_name(text) == "score_topk"
    assert tr.op_label(text) == "score_topk (f32[977,8,161]"
    assert tr.op_name("%copy.2 = s32[4]{0} copy(s32[4]{0} %x)") == "copy"
    assert tr.op_name("%rerank_fused_intt = s32[2]{0} custom-call()") == \
        "rerank_fused_intt"
    # an op that only consumes a kernel's output is not the kernel
    assert tr.kernel_of(ev("%get-tuple-element.3 = f32[2]{0} "
                           "get-tuple-element(%score_topk.1), index=0", 0, 1),
                        ["score_topk"]) is None


def test_recorded_v5e_trace():
    """Three calls of the score-top-k' kernel on one v5e, each after 5 ms
    in a ``chipbench/encrypt`` annotation and followed by 2 ms outside any
    annotation, all inside ``chipbench/window``."""
    s = tr.reduce_file(str(FIXTURE), ["score_topk", "rerank_fused_intt"])
    assert s.devices == 1
    assert s.kernel_calls == {"score_topk": 3}
    assert s.kernel_s["score_topk"] == pytest.approx(7.1303e-05)
    assert 0 < s.busy_s < s.window_s == pytest.approx(0.026277409)
    assert s.idle_share > 0.99
    # the three long gaps: host encryption, then the host outside the
    # engine (the 2 ms sleep of the last round, up to the window's end)
    assert [name for name, _ in s.idle_gaps[:4]] == [
        "encrypt", "encrypt", "encrypt", "none"]
    assert s.idle_gaps[0][1] > 0.005
    assert s.top_ops[0][0] == "score_topk (f32[4,2,16]"
