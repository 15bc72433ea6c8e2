"""Program spans and counters of the serving engine (``repro.serve.tracing``):
the disabled log records nothing, an enabled one nests every phase inside
its tick, the counters agree with the spans, tracing leaves the served
tokens alone, and the device programs carry their names and scopes."""
import asyncio
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.parallel import ParallelContext
from repro.serve import AsyncServeFrontend, PagedServeEngine, Request
from repro.serve.engine import EngineMetrics
from repro.serve.tracing import SpanLog
from repro.spec import SpeculativeServeEngine

PCTX = ParallelContext(None)
TICK_PHASES = {"admit", "pages", "inputs", "prefill", "decode", "sample"}


@pytest.fixture(scope="module")
def llama():
    cfg = get_config("llama3-8b", smoke=True)
    bundle = build_model(cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(0))


def _reqs(n=4, prompt_len=9, max_new=5):
    return [Request(rid=i, prompt=[1 + i] + [2] * (prompt_len - 1),
                    max_new_tokens=max_new) for i in range(n)]


def _engine(llama, cls=PagedServeEngine, **kw):
    bundle, params = llama
    kw = dict(dict(slots=3, page_size=4, num_pages=24, prefill_chunk=4), **kw)
    return cls(bundle, params, PCTX, **kw)


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    return [r.output for r in reqs]


@pytest.fixture(scope="module")
def traced(llama):
    """One drained run with the log on: the engine and its requests."""
    eng = _engine(llama)
    eng.spans.enable()
    reqs = _reqs()
    _drain(eng, reqs)
    return eng, reqs


def test_disabled_log_records_nothing(llama):
    log = SpanLog()
    assert not log.enabled
    assert log.span("step", tick=0) is log.span("admit")   # one shared no-op
    with log.timed("decode", rows=2) as t:
        pass
    assert t.seconds >= 0.0 and log.records() == []
    eng = _engine(llama)
    _drain(eng, _reqs(2))
    assert eng.spans.records() == []
    assert eng.metrics.decode_time_s > 0 and eng.metrics.prefill_time_s > 0


def test_ids_unique_and_children_nest_in_their_parent(traced):
    eng, _ = traced
    spans = eng.spans.records()
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans) and 0 not in by_id
    for s in spans:
        assert s.t0 <= s.t1
        if s.parent_id:
            p = by_id[s.parent_id]
            assert p.t0 <= s.t0 and s.t1 <= p.t1, (s, p)
    phases = [s for s in spans if s.name in TICK_PHASES]
    assert {s.name for s in phases} == TICK_PHASES
    assert all(by_id[s.parent_id].name == "step" for s in phases)
    steps = eng.spans.records("step")
    assert [s.attrs["tick"] for s in steps] == list(range(eng.metrics.ticks))
    assert all(s.parent_id == 0 for s in steps)
    assert sum(s.attrs["admitted"] for s in eng.spans.records("admit")) == 4
    assert {s.attrs["phase"] for s in eng.spans.records("sample")} == {
        "prefill", "decode"}


def test_counters_agree_with_spans(traced):
    eng, reqs = traced
    m, log = eng.metrics, eng.spans
    dec, pre = log.records("decode"), log.records("prefill")
    assert m.decode_calls == len(dec) > 0
    assert m.decode_tokens == sum(s.attrs["rows"] for s in dec)
    assert m.prefill_calls == len(pre) > 0
    assert m.prefill_tokens == sum(s.attrs["new_tokens"] for s in pre)
    # the timers and the spans share their clock reads
    assert m.decode_time_s == pytest.approx(sum(s.t1 - s.t0 for s in dec))
    assert m.prefill_time_s == pytest.approx(sum(s.t1 - s.t0 for s in pre))
    # a request's chunks are cached in order, from its first position
    for r in reqs:
        mine = [s for s in pre if s.attrs["rid"] == r.rid]
        assert mine[0].attrs["cached_tokens"] == 0
        assert sum(s.attrs["new_tokens"] for s in mine) == len(r.prompt)
    # cached_tokens of a decode call: the live lengths of its rows
    assert all(s.attrs["cached_tokens"] >= s.attrs["rows"] * 9 for s in dec)


def test_admission_time_between_submit_and_first_token(traced):
    _, reqs = traced
    for r in reqs:
        assert 0 < r.submitted_at <= r.admitted_at <= r.first_token_at


def test_admission_time_kept_across_preemption(llama):
    eng = _engine(llama, slots=4, num_pages=10)
    reqs = _reqs(5, prompt_len=6, max_new=8)
    for r in reqs:
        eng.submit(r)
    first = {}
    for _ in range(200):
        for r in reqs:
            if r.admitted_at and r.rid not in first:
                first[r.rid] = r.admitted_at
        if eng.step() == 0 and not eng.sched.waiting:
            break
    assert eng.metrics.preemptions > 0
    assert all(r.done for r in reqs)
    assert {r.rid: r.admitted_at for r in reqs} == first


def test_tokens_identical_with_log_on_and_off(llama, traced):
    _, on_reqs = traced
    off = _drain(_engine(llama), _reqs())
    assert off == [r.output for r in on_reqs]


def test_speculative_tick_spans(llama):
    eng = _engine(llama, cls=SpeculativeServeEngine, spec_k=2)
    eng.spans.enable()
    reqs = _reqs(3, max_new=8)
    out = _drain(eng, reqs)
    m, log = eng.metrics, eng.spans
    verify, draft = log.records("verify"), log.records("draft")
    assert m.decode_calls == len(verify) > 0 and log.records("decode") == []
    assert m.draft_time_s == pytest.approx(sum(s.t1 - s.t0 for s in draft))
    assert m.decode_time_s == pytest.approx(sum(s.t1 - s.t0 for s in verify))
    by_id = {s.id: s for s in log.records()}
    assert all(by_id[s.parent_id].name == "step" for s in verify + draft)
    assert out == _drain(_engine(llama), _reqs(3, max_new=8))


def test_front_end_spans(llama):
    eng = _engine(llama)
    eng.spans.enable()

    async def go():
        async with AsyncServeFrontend(eng) as front:
            streams = [await front.submit([3, 4, 5], max_new_tokens=4)
                       for _ in range(2)]
            return [await s.drain() for s in streams]

    outs = asyncio.run(go())
    subs = eng.spans.records("submit")
    assert [s.attrs["rid"] for s in subs] == [0, 1]
    assert all(s.parent_id == 0 for s in subs)
    pumps = eng.spans.records("pump")
    assert sum(s.attrs["tokens"] for s in pumps) == sum(map(len, outs)) == 8
    assert all(s.parent_id == 0 for s in pumps)


def test_log_is_bounded():
    log = SpanLog()
    log.enable(capacity=3)
    for i in range(5):
        with log.span("step", tick=i):
            pass
    assert [s.attrs["tick"] for s in log.records()] == [2, 3, 4]


def test_page_utilization_is_aggregated():
    m = EngineMetrics()
    assert m.peak_page_utilization == 0.0 == m.mean_page_utilization
    for u in (0.25, 0.75, 0.5):
        m.observe_utilization(u)
    assert m.peak_page_utilization == 0.75
    assert m.mean_page_utilization == pytest.approx(0.5)
    s = m.summary()
    assert s["peak_page_utilization"] == 0.75
    assert s["mean_page_utilization"] == 0.5


def _decode_args(eng):
    b = eng.slots
    return (eng.params, eng.cache, jnp.zeros((b, 1), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.ones((b,), jnp.int32),
            jnp.asarray(eng._tables(range(b))))


def _code(hlo: str) -> str:
    """A compiled module's computations without op metadata (names, source
    locations) or the stack-frame tables, each instruction and computation
    renamed by its first appearance."""
    text = "\n".join(re.sub(r", metadata=\{[^}]*\}", "", line)
                     for line in hlo.splitlines()
                     if line.lstrip().startswith(("%", "ROOT", "ENTRY", "}")))
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(), f"%v{len(names)}"),
                  text)


def test_programs_are_named_and_scoped(llama):
    eng = _engine(llama)
    args = _decode_args(eng)
    for fn, name in ((eng._prefill, "serve_prefill"),
                     (eng._decode_step, "serve_decode"),
                     (eng._verify, "serve_verify")):
        assert f"jit_{name}" in fn.lower(*args).as_text()
    hlo = eng._decode_step.lower(*args).compile().as_text()
    for scope in ("attn", "kv_pages", "mlp", "lm_head"):
        assert re.search(rf'op_name="jit\(serve_decode\)/[^"]*\b{scope}/',
                         hlo), scope


def test_scopes_leave_the_compiled_code_alone(llama, monkeypatch):
    eng = _engine(llama)
    args = _decode_args(eng)
    scoped = eng._decode_step.lower(*args).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _engine(llama)
    bare = plain._decode_step.lower(*_decode_args(plain)).compile().as_text()
    assert "kv_pages" in scoped and "kv_pages" not in bare
    assert _code(scoped) == _code(bare)
    a = eng._decode_step(*args)[0]
    b = plain._decode_step(*_decode_args(plain))[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
