"""Logits of a served request against the model's plain forward pass.

:func:`served_logits` runs one greedy request through a paged engine and
records the logits rows the engine's own jitted steps produced for it: the
last real row of the final prefill chunk, then the request's row of each
decode tick.  :func:`forward_logits` computes the same rows with
``bundle.forward`` over the prompt plus the emitted tokens (one causal
pass, no cache).  On a correct engine the two differ only by rounding —
the same bf16 arithmetic in another order and shape (chunked vs whole
attention, paged gather, recurrent scan vs step) — so
:func:`logits_error` between them is small against the logit range, while
a wrong page, position or state shows as an error of the order of the
logits themselves.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import ParallelContext
from .scheduler import Request


def served_logits(engine, prompt: Sequence[int],
                  steps: int) -> Tuple[List[int], np.ndarray]:
    """Serve ``prompt`` for ``steps`` greedy tokens on an idle paged
    ``engine``; return (tokens, float32 logits rows (steps, vocab)) where
    row ``i`` is the distribution token ``i`` was drawn from."""
    if any(r is not None for r in engine.active) or engine.sched.waiting:
        raise ValueError("served_logits needs an idle engine")
    req = Request(rid=-1, prompt=list(prompt), max_new_tokens=steps)
    prefill_rows, decode_rows = [], []
    prefill, decode = engine._prefill, engine._decode_step

    def record_prefill(params, cache, tokens, lengths, counts, tables):
        logits, cache = prefill(params, cache, tokens, lengths, counts,
                                tables)
        prefill_rows.append(logits[0, int(counts[0]) - 1])
        return logits, cache

    def record_decode(params, cache, tokens, lengths, counts, tables):
        logits, cache = decode(params, cache, tokens, lengths, counts,
                               tables)
        decode_rows.append(logits[req.slot, 0])
        return logits, cache

    engine._prefill, engine._decode_step = record_prefill, record_decode
    try:
        engine.submit(req)
        engine.run_until_drained()
    finally:
        engine._prefill, engine._decode_step = prefill, decode
    rows = [prefill_rows[-1]] + decode_rows
    if not req.done or len(rows) != steps:
        raise RuntimeError(f"served {len(req.output)} of {steps} tokens "
                           f"with {len(rows)} logits rows")
    return list(req.output), np.stack([np.asarray(r, np.float32)
                                       for r in rows])


def forward_logits(bundle, params, prompt: Sequence[int],
                   tokens: Sequence[int]) -> np.ndarray:
    """The rows :func:`served_logits` returns, from one ``bundle.forward``
    over ``prompt + tokens[:-1]``."""
    seq = jnp.asarray([list(prompt) + list(tokens[:-1])], jnp.int32)
    fwd = jax.jit(lambda p, t: bundle.forward(p, {"tokens": t},
                                              ParallelContext(None)))
    logits = fwd(params, seq)[0, len(prompt) - 1:]
    return np.asarray(logits, np.float32)


def logits_error(got: np.ndarray, ref: np.ndarray,
                 vocab_size: int) -> Tuple[float, float]:
    """(max absolute error, max absolute error / max |ref|) over the live
    vocab (padded slots are masked to -1e30 on both sides).  The relative
    figure is taken against the logit range, not elementwise, because
    logits near zero make an elementwise ratio meaningless."""
    got, ref = got[..., :vocab_size], ref[..., :vocab_size]
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return float("inf"), float("inf")
    err = float(np.max(np.abs(got - ref)))
    return err, err / float(np.max(np.abs(ref)))
