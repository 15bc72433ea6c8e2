"""The reduction from a trace to device time, idle share and the idle gaps
by what the host was doing: on a hand-made trace whose answers are
worked out by hand, and on a short trace recorded on a TPU v5e."""
import gzip
import json

import pytest

from chipbench import trace
from _smoke import ROOT

RECORDED = ROOT / "tests" / "chipbench" / "data" / "chat_trace.json.gz"


def hand_trace():
    spans = [["step", 0, 0, 100], ["decode", 1, 10, 60],
             ["prefill", 2, 65, 90], ["step", 3, 120, 200],
             ["decode", 4, 130, 180]]
    d = "/device:TPU:0"
    ops = [["fusion.1", 12, 30, d], ["fusion.2", 25, 58, d],
           ["fusion.1", 70, 88, d], ["dot.3", 135, 175, d],
           ["copy.4", 185, 190, d]]
    return trace.Trace(spans, ops, 1)


def test_union_and_intersect():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10),
                                                                (20, 25)]
    assert trace.length([(1, 4), (5, 8)]) == 6


def test_busy_and_idle_by_hand():
    t = hand_trace()
    assert t.window() == (0, 200)
    assert trace.busy(t, [(0, 200)]) == 46 + 18 + 40 + 5
    assert trace.idle_in_ticks(t) == pytest.approx(1 - 109 / 180)
    assert trace.device_ns_by_call(t, "decode") == {1: 46, 4: 40}
    assert trace.device_ns_by_call(t, "prefill") == {2: 18}


def test_idle_gaps_attributed_to_host_spans():
    gaps = trace.idle_gaps(hand_trace())
    # [0,12] and [58,70] inside step 0 outside its calls; [88,135] is
    # attributed at its midpoint 111.5, between steps; [175,185] and
    # [190,200] in step 3 after its decode call
    assert gaps == {"step_host": 12 + 12 + 10 + 10, "between_steps": 47}
    b = trace.breakdown(hand_trace())
    assert b["device_ops"][0] == ["dot.3", 40e-9]
    assert dict(b["device_ops"])["fusion.1"] == pytest.approx(36e-9)
    assert b["idle_gaps"][0] == ["between_steps", 47e-9]


def test_two_devices_are_averaged():
    t = hand_trace()
    t.ops = t.ops + [["fusion.9", 0, 200, "/device:TPU:1"]]
    t.unions = None
    assert trace.busy(t, [(0, 200)]) == (109 + 200) / 2


def test_recorded_trace():
    """Three engine steps of ``chatglm3-6b.chat`` traced on a TPU v5e."""
    with gzip.open(RECORDED, "rt") as f:
        t = trace.Trace.from_json(json.load(f))
    assert t.devices == 1
    kinds = {s[0] for s in t.spans}
    assert {"step", "decode", "prefill"} <= kinds
    w0, w1 = t.window()
    busy = trace.busy(t, [(w0, w1)])
    assert 0 < busy < w1 - w0
    # every idle nanosecond of the window is attributed to one host span
    assert sum(trace.idle_gaps(t).values()) == pytest.approx(w1 - w0 - busy)
    assert 0.0 < trace.idle_in_ticks(t) < 1.0
    for kind in ("decode", "prefill"):
        spans = {c: e - s for k, c, s, e in t.spans if k == kind}
        for call, ns in trace.device_ns_by_call(t, kind).items():
            assert 0 < ns <= spans[call]
    b = trace.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(" = " not in name for name, _ in b["device_ops"])
