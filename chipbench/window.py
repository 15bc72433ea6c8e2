"""Window arithmetic: what a run's request records say about its window.

All times are host ``time.perf_counter()`` seconds.  A request belongs to
the window when it was *due* inside it (open loop: its scheduled time;
closed loop: when its client sent it).  Tails are nearest-rank
percentiles over every sample, and a request with no first token counts
as a miss at the top of the TTFT tail.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


@dataclasses.dataclass
class RequestRecord:
    index: int
    due: float
    prompt_len: int
    max_new: int
    submitted: Optional[float] = None   # when ``submit`` returned
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None

    @property
    def finished(self) -> bool:
        return self.error is None and len(self.tokens) >= self.max_new

    @property
    def first_token(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


@dataclasses.dataclass
class Window:
    open: float
    close: float

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def holds(self, t: float) -> bool:
        return self.open <= t < self.close


def percentile(values: Sequence[float], pct: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``pct``%
    of the samples at or below it (``inf`` samples sort last)."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    return s[max(math.ceil(pct / 100.0 * len(s)) - 1, 0)]


def due_in(records: Sequence[RequestRecord], w: Window) -> List[RequestRecord]:
    return [r for r in records if w.holds(r.due)]


def output_tok_s(records: Sequence[RequestRecord], w: Window) -> float:
    """Every token emitted inside the window, over the window's length."""
    n = sum(1 for r in records for t in r.token_times if w.holds(t))
    return n / w.seconds


def itl_gaps(records: Sequence[RequestRecord], w: Window) -> List[float]:
    """Every gap between consecutive tokens of a request whose later token
    came inside the window, pooled over all requests."""
    return [b - a for r in records
            for a, b in zip(r.token_times, r.token_times[1:]) if w.holds(b)]


def ttfts(records: Sequence[RequestRecord], w: Window) -> List[float]:
    """First-token time minus due time of every request due in the window;
    ``inf`` for one that failed or has no first token."""
    return [(r.first_token - r.due) if (r.error is None and r.token_times)
            else math.inf for r in due_in(records, w)]


def submit_lags(records: Sequence[RequestRecord], w: Window) -> List[float]:
    """From due to ``submit`` returning, for the window's requests."""
    return [r.submitted - r.due for r in due_in(records, w)
            if r.submitted is not None]


def failed(records: Sequence[RequestRecord], w: Window) -> List[RequestRecord]:
    """Window requests that raised or got no token before the run ended
    (one still streaming is late, not failed)."""
    return [r for r in due_in(records, w)
            if r.error is not None or not r.token_times]
