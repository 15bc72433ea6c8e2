"""Paged serving engine + scheduler: FIFO/budget/preemption policy units,
paged-vs-contiguous token identity, oversubscription, metrics."""
import jax
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.parallel import ParallelContext
from repro.serve import PagedServeEngine, Request, ServeEngine
from repro.serve.scheduler import DECODING, PREFILLING, FifoScheduler

PCTX = ParallelContext(None)


# ------------------------------------------------------------ scheduler unit
class TestFifoScheduler:
    def _reqs(self, n, prompt_len=10):
        return [Request(rid=i, prompt=[1] * prompt_len) for i in range(n)]

    def test_admission_is_fifo(self):
        s = FifoScheduler(prefill_chunk=4)
        reqs = self._reqs(4)
        for r in reqs:
            s.submit(r)
        placed = s.admit([7, 3])
        assert [(slot, r.rid) for slot, r in placed] == [(7, 0), (3, 1)]
        assert [r.rid for r in s.waiting] == [2, 3]
        assert all(r.state == PREFILLING for _, r in placed)
        # admission order is recorded for preemption/planning
        assert placed[0][1].admit_seq < placed[1][1].admit_seq

    def test_preempted_requeues_at_front(self):
        s = FifoScheduler(prefill_chunk=4)
        reqs = self._reqs(3)
        for r in reqs:
            s.submit(r)
        (slot, victim), = s.admit([0])
        victim.output = [42]
        victim.prefill_pos = 7
        s.requeue_preempted(victim)
        assert [r.rid for r in s.waiting] == [0, 1, 2]
        assert victim.prefill_pos == 0 and victim.preemptions == 1
        # recompute covers prompt + already-generated tokens
        assert victim.prefill_tokens() == victim.prompt + [42]

    def test_prefill_plan_respects_budget_and_order(self):
        s = FifoScheduler(prefill_chunk=4, prefill_budget=10)
        reqs = self._reqs(4, prompt_len=6)
        for r in reqs:
            s.submit(r)
        placed = s.admit([0, 1, 2, 3])
        plan = s.prefill_plan([r for _, r in placed])
        # admission order; 4 + 4 + 2 = 10-token budget, 4th request starved
        assert [(r.rid, n) for r, n in plan] == [(0, 4), (1, 4), (2, 2)]

    def test_prefill_plan_final_partial_chunk(self):
        s = FifoScheduler(prefill_chunk=8)
        (req,) = self._reqs(1, prompt_len=6)
        s.submit(req)
        s.admit([0])
        req.prefill_pos = 4
        assert s.prefill_plan([req]) == [(req, 2)]

    def test_preemption_victim_is_youngest(self):
        s = FifoScheduler(prefill_chunk=4)
        reqs = self._reqs(3)
        for r in reqs:
            s.submit(r)
        active = [r for _, r in s.admit([0, 1, 2])]
        assert s.preemption_victim(active).rid == 2
        assert s.preemption_victim(active, exclude=active[2]).rid == 1
        assert s.preemption_victim([]) is None


# ----------------------------------------------------------- engine (smoke)
@pytest.fixture(scope="module")
def llama():
    cfg = get_config("llama3-8b", smoke=True)
    bundle = build_model(cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(0))


def _trace(n, prompt_len=5, max_new=6):
    return [Request(rid=i, prompt=[1 + i] + [2] * (prompt_len - 1),
                    max_new_tokens=max_new) for i in range(n)]


def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    return [r.output for r in reqs]


def test_oversubscription_preempts_and_recomputes_identically(llama):
    """Scheduler fairness under page pressure: a pool too small for the
    offered load must still drain every request, via youngest-first
    preemption, without changing any request's tokens."""
    bundle, params = llama
    tight = _trace(5, prompt_len=6, max_new=8)
    eng = PagedServeEngine(bundle, params, PCTX, slots=4, page_size=4,
                           num_pages=10, prefill_chunk=4)
    tight_out = _run(eng, tight)
    assert eng.metrics.preemptions > 0
    assert all(r.done for r in tight)
    roomy = _trace(5, prompt_len=6, max_new=8)
    eng2 = PagedServeEngine(bundle, params, PCTX, slots=4, page_size=4,
                            num_pages=64, prefill_chunk=4)
    roomy_out = _run(eng2, roomy)
    assert eng2.metrics.preemptions == 0
    assert tight_out == roomy_out
    # FIFO fairness: completion order follows submit order
    finish = [r.finished_at for r in tight]
    assert finish == sorted(finish)

def test_submit_rejects_request_larger_than_pool(llama):
    bundle, params = llama
    eng = PagedServeEngine(bundle, params, PCTX, slots=2, page_size=4,
                           num_pages=4, prefill_chunk=4)
    with pytest.raises(ValueError, match="exceeds per-request capacity"):
        eng.submit(Request(rid=0, prompt=[1] * 10, max_new_tokens=10))


def test_submit_rejects_empty_prompt(llama):
    """An empty prompt would never be planned by prefill_plan (zero tokens
    to cache), leaving the request PREFILLING forever — reject at submit."""
    bundle, params = llama
    eng = PagedServeEngine(bundle, params, PCTX, slots=2, page_size=4,
                           num_pages=4, prefill_chunk=4)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(rid=0, prompt=[], max_new_tokens=4))
    slot_eng = ServeEngine(bundle, params, PCTX, slots=2, max_seq=32)
    with pytest.raises(ValueError, match="empty prompt"):
        slot_eng.submit(Request(rid=0, prompt=[], max_new_tokens=4))


def test_engine_metrics_accounting(llama):
    bundle, params = llama
    reqs = _trace(3, prompt_len=5, max_new=4)
    eng = PagedServeEngine(bundle, params, PCTX, slots=3, page_size=8,
                           num_pages=12, prefill_chunk=4)
    for r in reqs:
        eng.submit(r)
    m = eng.run_until_drained()
    assert m.requests_done == 3
    assert m.prefill_tokens == sum(len(r.prompt) for r in reqs)
    # first token comes from prefill logits; the rest from decode ticks
    assert m.decode_tokens == sum(len(r.output) - 1 for r in reqs)
    assert len(m.ttfts) == 3 and all(t > 0 for t in m.ttfts)
    assert m.prefill_time_s > 0 and m.decode_time_s > 0
    assert 0 < m.peak_page_utilization <= 1
    # every request's pages flushed back on completion
    assert eng.kv.used_pages == 0
    s = m.summary()
    assert s["requests_done"] == 3 and s["preemptions"] == 0


def test_paged_engine_rejects_unsupported_families():
    """Audio (enc-dec) has neither paged KV nor a state pool — rejected;
    recurrent-state families (e.g. zamba2) construct via the state cache."""
    cfg = get_config("whisper-large-v3", smoke=True)
    bundle = build_model(cfg)
    assert not bundle.supports_paged_serving
    with pytest.raises(ValueError, match="no paged"):
        PagedServeEngine(bundle, None, PCTX)
    with pytest.raises(ValueError, match="no paged"):
        bundle.init_paged_cache(8, 8)

    zcfg = get_config("zamba2-1.2b", smoke=True)
    zbundle = build_model(zcfg)
    assert not zbundle.supports_paged_kv       # pages live inside the
    assert zbundle.supports_paged_state        # combined hybrid contract
    assert zbundle.supports_paged_serving
    zparams = zbundle.init_params(jax.random.PRNGKey(0))
    eng = PagedServeEngine(zbundle, zparams, PCTX, slots=2, page_size=8,
                           num_pages=8, prefill_chunk=4)
    assert eng.state is not None and eng.state.pool_slots == 2 + 2 * 2
    with pytest.raises(ValueError, match="prefix_sharing"):
        PagedServeEngine(zbundle, zparams, PCTX, slots=2, page_size=8,
                         num_pages=8, prefix_sharing=True)


def test_request_lifecycle_states(llama):
    """queued -> prefilling -> decoding -> done, one tick at a time."""
    bundle, params = llama
    eng = PagedServeEngine(bundle, params, PCTX, slots=1, page_size=8,
                           num_pages=8, prefill_chunk=4)
    req = Request(rid=0, prompt=[1] * 8, max_new_tokens=3)
    eng.submit(req)
    eng.step()                  # admit + first 4-token chunk
    assert req.state == PREFILLING and req.prefill_pos == 4
    eng.step()                  # final chunk -> first token -> decoding
    assert req.state == DECODING and len(req.output) >= 1
    while not req.done:
        eng.step()
    assert len(req.output) == 3 and eng.kv.used_pages == 0


# ------------------------------------------------ logits vs plain forward
#: max |served - forward| / max |forward| per family at smoke size.  The
#: dense and rwkv6 steps are the forward's own bf16 operations in another
#: shape (bitwise or near it on the CPU); the hybrid's engine recurrence and
#: the forward's chunked SSD round differently at every bf16 mixer boundary
#: and sit up to ~0.32 apart, as far as each sits from a float32 forward.
_LOGITS_TOL = {"chatglm3-6b": 0.05, "rwkv6-3b": 0.05, "zamba2-1.2b": 0.4}


@pytest.mark.parametrize("arch", sorted(_LOGITS_TOL))
def test_served_logits_match_forward(arch):
    import numpy as np

    from repro.serve.logits_check import (forward_logits, logits_error,
                                          served_logits)
    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    eng = PagedServeEngine(bundle, params, PCTX, slots=2, page_size=8,
                           prefill_chunk=8)
    prompt = np.random.default_rng(0).integers(2, cfg.vocab_size,
                                               37).tolist()
    tokens, got = served_logits(eng, prompt, 6)
    assert got.shape == (6, cfg.padded_vocab)
    assert tokens == [int(i) for i in got.argmax(-1)]
    ref = forward_logits(bundle, params, prompt, tokens)
    _, rel = logits_error(got, ref, cfg.vocab_size)
    assert rel <= _LOGITS_TOL[arch], rel
    # a wrong position is caught: shift the reference by one token
    _, off = logits_error(got[1:], ref[:-1], cfg.vocab_size)
    assert off > _LOGITS_TOL[arch], off


def test_launcher_serves_smoke_config(capsys, monkeypatch):
    from repro.launch import compile_cache, serve
    # keep this process's JAX config as the other tests expect it
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    serve.main(["--arch", "chatglm3-6b", "--smoke", "--requests", "3",
                "--max-new", "4"])
    assert "served 3/3 requests" in capsys.readouterr().out


def test_compile_cache_placed_from_the_environment(monkeypatch, tmp_path):
    from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
        assert CACHE_DIR.parent.joinpath("pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
