"""The seeded traffic generator and the window arithmetic."""
import json
import math
import statistics

import numpy as np
import pytest

from chipbench import traffic, window
from chipbench.window import RequestRecord, Window
from _smoke import ROOT

OPEN = {"loop": "open", "rate_per_s": 2.0, "warmup_s": 5.0,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.8,
                   "min": 32, "max": 2048},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.8,
                   "min": 16, "max": 512}}
CLOSED = {"loop": "closed", "clients": 4, "client_spacing_s": 0.5,
          "prompt": {"dist": "uniform", "min": 3072, "max": 6144},
          "output": {"dist": "lognormal", "median": 192, "sigma": 0.5,
                     "min": 64, "max": 512}}
BIG_SEED = 2**31 + 987654321


@pytest.mark.parametrize("seed", [0, 17, BIG_SEED])
def test_same_seed_same_requests(seed):
    assert traffic.closed_pool(CLOSED, seed) == traffic.closed_pool(CLOSED, seed)
    assert traffic.prompt_tokens(seed, 3, 50, 1000) == \
        traffic.prompt_tokens(seed, 3, 50, 1000)
    assert traffic.prompt_tokens(seed, 3, 50, 1000) != \
        traffic.prompt_tokens(seed + 1, 3, 50, 1000)


def test_open_loop_window_holds_its_own_quantiles():
    """Every seed's open loop is one schedule; its window holds exactly
    the quantiles of its own arrivals and sizes."""
    a = traffic.open_loop(OPEN, 30.0)
    assert a == traffic.open_loop(OPEN, 30.0)
    assert len(a) == 10 + 60 + 2 * traffic.DRAIN_OFFER_S
    win = [r for r in a if 5.0 <= r.due_s < 35.0]
    assert len(win) == 60 and win[0].due_s == 5.0
    rng = np.random.default_rng(0)
    want = sorted(traffic.lengths(OPEN["output"], 60, rng))
    assert sorted(r.max_new for r in win) == want
    # the window's gaps, the one to its close included, are the 60
    # exponential quantiles scaled to fill its 30 seconds
    gaps = np.diff([r.due_s for r in win] + [35.0])
    q = -np.log1p(-(np.arange(60) + 0.5) / 60)
    np.testing.assert_allclose(sorted(gaps), sorted(30.0 * q / q.sum()))


def test_closed_pool_blocks_hold_the_same_sizes():
    a, b = traffic.closed_pool(CLOSED, 3), traffic.closed_pool(CLOSED, 4)
    n = traffic.CLOSED_BLOCK
    for k in (0, 5 * n):
        assert sorted(r.max_new for r in a[k:k + n]) == \
            sorted(r.max_new for r in b[k:k + n])
    assert [r.max_new for r in a[:n]] != [r.max_new for r in b[:n]]


@pytest.mark.parametrize("spec", [OPEN, CLOSED])
def test_lengths_within_clips(spec):
    reqs = (traffic.open_loop(spec, 30.0) if spec["loop"] == "open"
            else traffic.closed_pool(spec, 5))
    for key, field in (("prompt", "prompt_len"), ("output", "max_new")):
        vals = [getattr(r, field) for r in reqs]
        assert min(vals) >= spec[key]["min"] and max(vals) <= spec[key]["max"]
    if spec["loop"] == "open":
        med = np.median([r.prompt_len for r in reqs])
        assert 300 < med < 470          # lognormal median 384
        rate = len(reqs) / reqs[-1].due_s
        assert rate == pytest.approx(2.0, rel=0.1)


def test_prompt_tokens_in_vocab():
    toks = traffic.prompt_tokens(BIG_SEED, 0, 5000, 300)
    assert len(toks) == 5000 and min(toks) >= 2 and max(toks) < 300


def test_client_starts():
    assert list(traffic.client_starts(CLOSED)) == [0.0, 0.5, 1.0, 1.5]


def _rec(i, due, times, max_new=None, error=None):
    r = RequestRecord(i, due, 10, max_new if max_new is not None else len(times))
    r.token_times = list(times)
    r.tokens = [1] * len(times)
    r.submitted = due + 0.001 * i
    r.error = error
    return r


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert window.percentile(vals, 95) == 95
    assert window.percentile(vals, 90) == 90
    assert window.percentile([5.0], 95) == 5.0
    assert window.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        window.percentile([], 50)


def test_ttft_misses_at_the_top_of_the_tail():
    w = Window(10.0, 20.0)
    recs = [_rec(i, 10.0 + i, [10.0 + i + 0.1 * (i + 1)]) for i in range(9)]
    recs.append(_rec(9, 19.0, [], max_new=4))                # no first token
    recs.append(_rec(10, 5.0, [5.5]))                          # before window
    recs.append(_rec(11, 15.5, [], max_new=3, error="boom"))   # failed
    t = window.ttfts(recs, w)
    assert len(t) == 11
    assert sorted(t)[-2:] == [math.inf, math.inf]
    assert window.percentile(t, 90) == math.inf       # 2 of 11 missed
    assert window.percentile(t, 50) == pytest.approx(0.6)
    assert [r.index for r in window.failed(recs, w)] == [9, 11]


def test_gaps_pooled_and_tokens_counted_inside_the_window():
    w = Window(10.0, 12.0)
    a = _rec(0, 9.0, [9.5, 10.2, 10.4, 12.5])
    b = _rec(1, 10.5, [10.6, 11.0, 11.9])
    gaps = window.itl_gaps([a, b], w)
    # a: 10.2-9.5 and 10.4-10.2 end inside; 12.5 ends after the close
    assert sorted(np.round(gaps, 6)) == sorted(np.round([0.7, 0.2, 0.4, 0.9], 6))
    assert window.output_tok_s([a, b], w) == pytest.approx(5 / 2.0)
    assert len(window.submit_lags([a, b], w)) == 1    # only b was due inside


def test_committed_spreads_follow_their_source():
    """Each lognormal spread of a committed traffic mix is the one that
    its ``lengths_source`` gives: ln(P90 / P50) over the normal's P90."""
    z90 = statistics.NormalDist().inv_cdf(0.9)
    for path in (ROOT / "chipbench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        for key in ("prompt", "output"):
            if mix[key]["dist"] != "lognormal":
                continue
            p50, p90 = mix["lengths_source"][f"{key}_p50_p90"]
            assert mix[key]["sigma"] == pytest.approx(
                math.log(p90 / p50) / z90, abs=5e-4), (path.name, key)
