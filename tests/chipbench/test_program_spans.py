"""The program's own spans against the benchmark's wrappers, on the smoke
cell on the CPU, and the reader of the engine tick's host time on a
hand-made run whose answer is worked out by hand."""
import io
import types

import pytest

from chipbench import cell as cellmod
from chipbench import harness, spans
from chipbench.window import Window
from _smoke import ROOT, smoke_cell

SEED = 2**31 + 91


def test_program_spans_hold_the_wrappers_calls(monkeypatch):
    """One smoke run with the engine's span log on: the program's spans
    see the calls the benchmark's wrappers see, each wrapper interval
    inside its program span, with the same rows."""
    cell = smoke_cell("chatglm3-6b.chat", monkeypatch)
    logs, runs = [], []
    build, make_run = harness.build, harness.Run

    def traced_build(*a, **k):
        engine, init_s = build(*a, **k)
        engine.spans.enable()
        logs.append(engine.spans)
        return engine, init_s

    def keep_run(*a, **k):
        runs.append(make_run(*a, **k))
        return runs[-1]

    monkeypatch.setattr(harness, "build", traced_build)
    monkeypatch.setattr(harness, "Run", keep_run)
    harness.run_cell(cell, SEED, 1.5, False, 0.0, out=io.StringIO())
    calls = runs[0].calls
    t_first = calls[0].t0                  # the wrappers start after warm-up
    program = {k: [s for s in logs[0].records(k) if s.t0 >= t_first]
               for k in ("step", "prefill", "decode")}
    wrapped = {k: [c for c in calls if c.kind == k]
               for k in (spans.STEP, spans.PREFILL, spans.DECODE)}
    for kind in ("prefill", "decode"):
        assert len(program[kind]) == len(wrapped[kind]) > 0
        for s, c in zip(program[kind], wrapped[kind]):
            assert s.t0 <= c.t0 and c.t1 <= s.t1
            rows = c.rows()
            if kind == "decode":
                assert s.attrs["rows"] == len(rows)
                assert s.attrs["cached_tokens"] == sum(a for a, _ in rows)
            else:
                assert rows == [(s.attrs["cached_tokens"],
                                 s.attrs["new_tokens"])]
    # the wrapper of step encloses the program's step span
    assert len(program["step"]) == len(wrapped["step"])
    for s, c in zip(program["step"], wrapped["step"]):
        assert c.t0 <= s.t0 and s.t1 <= c.t1


def _call(kind, t0, t1):
    c = spans.Call(kind, 0, t0)
    c.t1 = t1
    return c


def _run(calls=()):
    return harness.Run(types.SimpleNamespace(root=ROOT), None, {}, None,
                       Window(0.0, 100.0), [], list(calls), 0.0)


def test_step_host_ms_by_hand():
    read = cellmod.reader(ROOT, "step.host_ms")
    calls = [_call("step", 0, 10), _call("decode", 2, 8),
             _call("step", 10, 20), _call("prefill", 11, 13),
             _call("decode", 14, 19), _call("step", 20, 23)]
    # host time per step: 10 - 6, 10 - 2 - 5, 3; the median
    assert read(_run(calls)) == pytest.approx(3e3)
    assert read(_run()) is None
