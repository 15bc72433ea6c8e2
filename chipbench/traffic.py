"""The one traffic generator: a traffic file's parameters plus a seed give
the requests of a run.

Lengths and gaps are the quantiles of their distributions at
``(i + 0.5) / n``, in an order drawn at random, so two seeds do the same
work.  An open loop draws them separately for the warm-up, the window and
the time after it, so every window holds the same arrivals and sizes, and
its schedule is the same for every seed: the order of arrivals sets the
queue, and with it the TTFT tail, which moved by a third between seeds
while repeated runs of one seed agreed within a few percent.  A closed
loop regulates its own queue; its pool is made of blocks of
``CLOSED_BLOCK`` requests that each hold the same sizes, in an order drawn
from the seed.  The seed always draws the prompt tokens.

A traffic file (``traffic/<name>.json``) holds:

* ``loop``: ``"open"`` (arrivals on a fixed stratified schedule:
  ``rate_per_s`` times the stretch's length of them, their gaps the
  quantiles of the exponential distribution at ``rate_per_s``, scaled to
  fill the stretch, in one order for every seed) or ``"closed"``
  (``clients`` that each send their next request when the last one
  completes, the first after ``i * client_spacing_s``);
* ``prompt`` and ``output``: a length distribution each, ``lognormal``
  (``median``, ``sigma``) or ``uniform``, clipped to ``[min, max]``;
* ``lengths_source``: where the length distributions come from;
* ``warmup_s``: traffic before the window opens;
* ``engine``: the ``PagedServeEngine`` settings of the cell;
* ``check_tokens``: served tokens the correctness sample holds at least;
* ``trace_s``: the traced stretch at the end of the window (``--trace 1``).
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Iterator, List

import numpy as np

#: open loop: arrivals are generated this far past the window's close, so
#: the load stays on while the window's last requests finish
DRAIN_OFFER_S = 60.0
#: open loop: the one order of every seed's arrivals and sizes
SCHEDULE_SEED = 0
#: closed loop: size of the request pool the clients draw from, in order,
#: in blocks that each hold the same sizes
CLOSED_POOL = 4096
CLOSED_BLOCK = 32


@dataclasses.dataclass
class RequestSpec:
    index: int
    due_s: float          # offset from the start of traffic (open loop)
    prompt_len: int
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles, clipped, permuted."""
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(v) for v in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(v)


def exponential_gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at ``rate``: quantiles, permuted."""
    return rng.permutation(-np.log1p(-_quantiles(n)) / rate)


def _segment(traffic: dict, start: float, length: float,
             rng: np.random.Generator) -> list:
    """``(due, prompt, output)`` of the arrivals in one stretch of an open
    loop: ``rate * length`` of them, the quantile gaps scaled to fill it."""
    n = max(round(traffic["rate_per_s"] * length), 1)
    gaps = exponential_gaps(traffic["rate_per_s"], n, rng)
    due = start + length * (np.cumsum(gaps) - gaps) / gaps.sum()
    return list(zip(due, lengths(traffic["prompt"], n, rng),
                    lengths(traffic["output"], n, rng)))


def open_loop(traffic: dict, seconds: float) -> List[RequestSpec]:
    """The schedule of an open-loop run: the warm-up, the window and
    ``DRAIN_OFFER_S`` past its close, each stretch with its own arrivals."""
    rng = np.random.default_rng(SCHEDULE_SEED)
    warm = traffic["warmup_s"]
    reqs = (_segment(traffic, 0.0, warm, rng)
            + _segment(traffic, warm, seconds, rng)
            + _segment(traffic, warm + seconds, DRAIN_OFFER_S, rng))
    return [RequestSpec(i, float(d), int(p), int(o))
            for i, (d, p, o) in enumerate(reqs)]


def closed_pool(traffic: dict, seed: int) -> List[RequestSpec]:
    """The requests closed-loop clients take in turn (``due_s`` unused)."""
    rng = np.random.default_rng(seed)
    sizes = [(p, o) for _ in range(CLOSED_POOL // CLOSED_BLOCK)
             for p, o in zip(lengths(traffic["prompt"], CLOSED_BLOCK, rng),
                             lengths(traffic["output"], CLOSED_BLOCK, rng))]
    return [RequestSpec(i, 0.0, int(p), int(o))
            for i, (p, o) in enumerate(sizes)]


def prompt_tokens(seed: int, index: int, n: int, vocab: int) -> List[int]:
    """Request ``index``'s prompt: ``n`` ids in ``[2, vocab)``."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(2, vocab, n).tolist()


def client_starts(traffic: dict) -> Iterator[float]:
    """Start offset of each closed-loop client."""
    for i in range(traffic["clients"]):
        yield i * traffic.get("client_spacing_s", 0.0)
