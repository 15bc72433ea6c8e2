"""Host spans around the engine's calls, and a compile counter.

:class:`Recorder` wraps one engine instance's ``step``, ``_prefill`` and
``_decode_step`` (instance attributes, so the program is not touched).
Each call is timed on the host clock around work that ends in
``block_until_ready``; with ``annotate`` it also opens a
``jax.profiler.TraceAnnotation`` named ``chipbench.<kind>`` carrying the
call's index, so the trace reduction can put device events inside it.
The small integer arguments of each model call (cached lengths and new
token counts per row) are kept as device arrays and read back after the
window.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

STEP, PREFILL, DECODE = "step", "prefill", "decode"


@dataclasses.dataclass
class Call:
    kind: str
    index: int
    t0: float
    t1: float = 0.0
    lengths: object = None     # device (B,) int32: tokens cached per row
    counts: object = None      # device (B,) int32: new tokens per row

    def rows(self):
        """``[(cached, new)]`` of the rows that get new tokens."""
        ln, cn = np.asarray(self.lengths), np.asarray(self.counts)
        return [(int(a), int(b)) for a, b in zip(ln, cn) if b > 0]


class Recorder:
    def __init__(self, engine, annotate: bool = False):
        self.calls: List[Call] = []
        self.annotate = annotate
        engine.step = self._wrap_step(engine.step)
        engine._prefill = self._wrap_model(engine._prefill, PREFILL)
        engine._decode_step = self._wrap_model(engine._decode_step, DECODE)

    def reset(self):
        self.calls = []

    def _open(self, kind: str):
        call = Call(kind, len(self.calls), 0.0)
        self.calls.append(call)
        span = None
        if self.annotate:
            import jax

            span = jax.profiler.TraceAnnotation(f"chipbench.{kind}",
                                                call=call.index)
            span.__enter__()
        call.t0 = time.perf_counter()
        return call, span

    @staticmethod
    def _close(call: Call, span) -> None:
        call.t1 = time.perf_counter()
        if span is not None:
            span.__exit__(None, None, None)

    def _wrap_step(self, fn):
        def step():
            call, span = self._open(STEP)
            try:
                return fn()
            finally:
                self._close(call, span)
        return step

    def _wrap_model(self, fn, kind: str):
        import jax

        def model_call(params, cache, tokens, lengths, counts, tables):
            call, span = self._open(kind)
            call.lengths, call.counts = lengths, counts
            try:
                out = fn(params, cache, tokens, lengths, counts, tables)
                jax.block_until_ready(out[0])
                return out
            finally:
                self._close(call, span)
        return model_call


class CompileMeter:
    """Backend compiles (with their end times), compile seconds and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.compiles: List[float] = []
        self.active = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles.append(time.perf_counter())

    def _event(self, event, **_):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.compiles if t0 <= t < t1)

    def stop(self) -> None:
        self.active = False


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")
