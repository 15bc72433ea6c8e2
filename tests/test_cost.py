"""repro.cost: hardware profiles, kernel/graph pricing, autotune pruning
parity, and the whole-graph schedule cache.

The contracts under test are the ones the rest of the repo leans on:

* pricing is strictly monotone in HBM traffic at fixed FLOPs (so pruning
  can rank by traffic, the paper's metric);
* a pruned sweep picks the exhaustive winner on every quick-suite tune
  space (or a config the model prices identically / timing can't
  distinguish within the recorded spread);
* the graph signature is a pure function of graph *structure* — stable
  across re-traces, sensitive to shape changes — so cached schedules can
  never be replayed onto a different geometry;
* cost-driven pass selection reproduces the fixed default pipeline's
  graph (drop decisions coincide with no-op rewrites), which is what
  keeps the serving matrix token-identical with the cost model on.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench import ConfigCache, all_specs, autotune, set_default_cache
from repro.bench.autotune import resolve_timing, time_stats
from repro.bench.config import BlockConfig, scoped_cache
from repro.bench.registry import get_spec
from repro.cost import (OVERLAP_LEAK, HardwareProfile, all_profiles,
                        candidate_passes, estimate_graph, estimate_kernel,
                        get_profile, graph_signature, lookup_schedule,
                        plan_graph, rank_candidates, select_passes,
                        store_schedule)
from repro.graph.passes import run_passes
from repro.graph.trace import trace

QUICK_SHAPES = {
    "apr_matmul": {"m": 64, "k": 128, "n": 64},
    "apr_matmul_fused": {"m": 64, "k": 128, "n": 64},
    "quant_matmul": {"m": 64, "k": 128, "n": 64},
    "quant_matmul_fused": {"m": 64, "k": 128, "n": 64},
    "apr_conv": {"b": 1, "h": 8, "w": 8, "c": 4, "hf": 3, "wf": 3,
                 "m": 8, "stride": 1, "padding": 1},
    "apr_conv_fused": {"b": 1, "h": 8, "w": 8, "c": 4, "hf": 3, "wf": 3,
                       "m": 8, "stride": 1, "padding": 1},
    "flash_decode": {"b": 2, "hq": 4, "hkv": 2, "d": 32, "s": 128},
    "flash_decode_paged": {"b": 2, "hq": 4, "hkv": 2, "d": 32,
                           "pages": 4, "ps": 32},
    "mamba2": {"b": 1, "t": 32, "h": 2, "p": 8, "n": 8},
    "rwkv6": {"b": 1, "t": 32, "h": 2, "d": 8},
}


@pytest.fixture
def cache(tmp_path):
    c = ConfigCache(tmp_path / "tune_cache.json")
    set_default_cache(c)
    yield c
    set_default_cache(None)


class TestProfiles:
    def test_default_and_registry(self):
        assert get_profile().name == "tpu_v5e"
        assert {"tpu_v5e", "cpu_interpret"} <= set(all_profiles())

    def test_env_selects(self, monkeypatch):
        monkeypatch.setenv("REPRO_HW_PROFILE", "cpu_interpret")
        assert get_profile().name == "cpu_interpret"
        # explicit name outranks the env
        assert get_profile("tpu_v5e").name == "tpu_v5e"

    @pytest.mark.parametrize("kind,expect", [("TPU v5 lite", "tpu_v5e"),
                                             ("TPU v9 imaginary", None)])
    def test_tpu_backend_resolves_device_kind(self, monkeypatch, kind,
                                              expect):
        import types

        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "devices", lambda: [
            types.SimpleNamespace(platform="tpu", device_kind=kind)])
        # on a chip the device decides, whatever the environment says
        monkeypatch.setenv("REPRO_HW_PROFILE", "cpu_interpret")
        if expect is None:
            with pytest.raises(KeyError, match="device kind"):
                get_profile()
        else:
            assert get_profile().name == expect
        assert get_profile("cpu_interpret").name == "cpu_interpret"

    def test_unknown_profile_raises(self):
        with pytest.raises(KeyError):
            get_profile("not_a_chip")

    def test_ridge_intensity(self):
        p = get_profile("tpu_v5e")
        assert p.ridge_intensity == pytest.approx(p.peak_flops / p.hbm_bw)


class TestKernelModel:
    def test_monotone_in_traffic(self):
        """More HBM traffic at fixed FLOPs must always price higher — the
        ordering pruning relies on (roofline max() alone would tie on the
        compute-bound side; the OVERLAP_LEAK term breaks it)."""
        spec = get_spec("apr_matmul")
        shape = QUICK_SHAPES["apr_matmul"]
        ests = [estimate_kernel(spec, shape, cfg)
                for cfg in spec.candidates(shape)]
        by_traffic = sorted(ests, key=lambda e: e.hbm_bytes)
        for a, b in zip(by_traffic, by_traffic[1:]):
            if b.hbm_bytes > a.hbm_bytes:
                assert b.predicted_s > a.predicted_s
            else:
                assert b.predicted_s == pytest.approx(a.predicted_s)

    def test_profile_scales_prediction(self):
        spec = get_spec("apr_matmul")
        shape = QUICK_SHAPES["apr_matmul"]
        cfg = spec.candidates(shape)[0]
        fast = estimate_kernel(spec, shape, cfg, profile=get_profile("tpu_v5e"))
        slow = estimate_kernel(spec, shape, cfg,
                               profile=get_profile("cpu_interpret"))
        assert slow.predicted_s > fast.predicted_s
        assert slow.profile == "cpu_interpret"

    def test_vmem_overflow_penalised(self):
        """A config whose tile working set exceeds the profile's VMEM must
        price worse than the same traffic without the spill."""
        spec = get_spec("apr_matmul")
        shape = {"m": 512, "k": 512, "n": 512}
        cfg = BlockConfig.make(block_m=256, block_n=256, block_k=512)
        tiny = dataclasses.replace(get_profile("tpu_v5e"), name="tiny_vmem",
                                   vmem_bytes=64 * 1024)
        ok = estimate_kernel(spec, shape, cfg)
        spilled = estimate_kernel(spec, shape, cfg, profile=tiny)
        assert ok.vmem_ok and not spilled.vmem_ok
        assert spilled.predicted_s > ok.predicted_s

    def test_rank_is_stable_and_complete(self):
        spec = get_spec("apr_matmul")
        shape = QUICK_SHAPES["apr_matmul"]
        cands = spec.candidates(shape)
        ranked = rank_candidates(spec, shape, cands)
        assert sorted(c.to_dict().items() for c, _ in ranked) \
            == sorted(c.to_dict().items() for c in cands)
        costs = [est.predicted_s for _, est in ranked]
        assert costs == sorted(costs)


class TestPruningParity:
    @pytest.mark.parametrize("kernel", sorted(QUICK_SHAPES))
    def test_pruned_matches_exhaustive(self, kernel, cache):
        """On every quick tune space the pruned sweep must select the
        exhaustive winner — identically, or a config the model prices
        within 1% (a genuine tie: either is a legitimate winner), or one
        whose measured time is within the runs' recorded timer spread."""
        spec = get_spec(kernel)
        shape = QUICK_SHAPES[kernel]
        cands = spec.candidates(shape)[:4]
        k = max(1, len(cands) // 2)
        ex = autotune(spec, shape, cache=cache, max_candidates=4)
        pr = autotune(spec, shape, cache=cache, max_candidates=4,
                      prune_top_k=k)
        assert ex.ok and pr.ok
        assert pr.pruned_from == len(cands)
        assert pr.n_timed <= k < pr.pruned_from
        assert pr.predicted_us is not None
        if pr.config == ex.config:
            return
        pred = {cfg: est.predicted_us
                for cfg, est in rank_candidates(spec, shape, cands)}
        tied = abs(pred[pr.config] - pred[ex.config]) \
            <= 0.01 * max(pred[pr.config], pred[ex.config])
        within_noise = abs(pr.us - ex.us) <= pr.spread_us + ex.spread_us
        assert tied or within_noise, (
            f"pruned {pr.config.to_dict()} vs exhaustive "
            f"{ex.config.to_dict()}: neither a predicted tie nor within "
            f"timer spread")

    def test_exhaustive_records_no_pruning(self, cache):
        spec = get_spec("apr_matmul")
        res = autotune(spec, QUICK_SHAPES["apr_matmul"], cache=cache)
        assert res.pruned_from is None
        assert res.n_timed == res.n_candidates - len(res.rejected)


class TestTiming:
    def test_env_overrides(self, monkeypatch):
        assert resolve_timing() == (3, 1)
        monkeypatch.setenv("REPRO_BENCH_ITERS", "7")
        monkeypatch.setenv("REPRO_BENCH_WARMUP", "0")
        assert resolve_timing() == (7, 0)
        # explicit args outrank the env
        assert resolve_timing(2, 5) == (2, 5)
        monkeypatch.setenv("REPRO_BENCH_ITERS", "junk")
        assert resolve_timing()[0] == 3

    def test_time_stats_spread(self, monkeypatch):
        monkeypatch.setenv("REPRO_BENCH_ITERS", "5")
        calls = []
        med, spread = time_stats(lambda: calls.append(1))
        assert len(calls) == 5 + 1     # default warmup 1 still applies
        assert med >= 0.0 and spread >= 0.0


def _mlp(x):
    w1 = jnp.ones((16, 32), jnp.float32)
    w2 = jnp.ones((32, 16), jnp.float32)
    b = jnp.arange(32, dtype=jnp.float32)
    h = jax.nn.relu(x @ w1 + b)
    return h @ w2


class TestSignature:
    def test_stable_across_retrace(self):
        x = jnp.ones((4, 16), jnp.float32)
        g1 = trace(_mlp, x, name="mlp")
        g2 = trace(_mlp, x, name="mlp")
        assert graph_signature(g1) == graph_signature(g2)

    def test_shape_sensitive(self):
        g1 = trace(_mlp, jnp.ones((4, 16), jnp.float32), name="mlp")
        g2 = trace(_mlp, jnp.ones((8, 16), jnp.float32), name="mlp")
        assert graph_signature(g1) != graph_signature(g2)

    def test_fusion_changes_signature(self):
        g = trace(_mlp, jnp.ones((4, 16), jnp.float32), name="mlp")
        sig = graph_signature(g)
        run_passes(g)
        assert graph_signature(g) != sig


class TestGraphModel:
    def test_fusion_reduces_predicted_traffic(self):
        x = jnp.ones((4, 16), jnp.float32)
        g = trace(_mlp, x, name="mlp")
        before = estimate_graph(g)
        run_passes(g)
        after = estimate_graph(g)
        assert after.intermediate_traffic < before.intermediate_traffic
        assert after.predicted_s < before.predicted_s
        assert before.flops == after.flops  # fusion moves bytes, not math

    def test_select_matches_default_pipeline(self):
        """Cost-driven selection must rebuild exactly the fixed pipeline's
        graph: a dropped pass is one that would not have changed the graph
        anyway (fusion only fires on a strict traffic win)."""
        x = jnp.ones((4, 16), jnp.float32)
        g_cost = trace(_mlp, x, name="mlp")
        g_fix = trace(_mlp, x, name="mlp")
        decision = select_passes(g_cost)
        run_passes(g_fix)
        assert graph_signature(g_cost) == graph_signature(g_fix)
        assert set(decision.passes) <= set(candidate_passes())
        kept = {d.name for d in decision.decisions if d.kept}
        assert kept == set(decision.passes)
        assert decision.traffic_reduction >= 1.0
        assert "keep" in decision.report()


class TestScheduleCache:
    def test_round_trip(self, cache):
        x = jnp.ones((4, 16), jnp.float32)
        g = trace(_mlp, x, name="mlp")
        sig = graph_signature(g)
        assert lookup_schedule(sig, cache) is None
        decision = select_passes(g, signature=sig)
        store_schedule(decision, cache)
        assert lookup_schedule(sig, cache) == decision.passes

    def test_stale_vocab_is_a_miss(self, cache):
        from repro.cost.schedule import _BACKEND, _DTYPE, SCHEDULE_KERNEL
        cfg = BlockConfig.make(renamed_pass=1)  # not the current registry
        cache.store(SCHEDULE_KERNEL, "sig", _DTYPE, _BACKEND, cfg)
        assert lookup_schedule("sig", cache) is None

    def test_plan_graph_hits_cache(self, cache):
        x = jnp.ones((4, 16), jnp.float32)
        with scoped_cache(cache):
            first = plan_graph(trace(_mlp, x, name="mlp"))
            second = plan_graph(trace(_mlp, x, name="mlp"))
        assert not first.cached and second.cached
        assert second.passes == first.passes
        assert second.fused.intermediate_traffic \
            == first.fused.intermediate_traffic

    def test_cost_model_off_skips_schedule(self, cache, monkeypatch):
        from repro.graph import compile_fn
        x = jnp.ones((4, 16), jnp.float32)
        with scoped_cache(cache):
            ex = compile_fn(_mlp, x)
            assert ex.schedule is not None
            monkeypatch.setenv("REPRO_COST_MODEL", "off")
            ex_off = compile_fn(_mlp, x)
        assert ex_off.schedule is None
        np.testing.assert_allclose(np.asarray(ex(x)), np.asarray(ex_off(x)),
                                   rtol=1e-6)
