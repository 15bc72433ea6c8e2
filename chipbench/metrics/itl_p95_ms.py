"""95th percentile of every gap between consecutive output tokens of every
request, pooled over the window (ms, host clock)."""
from chipbench import window


def read(run):
    gaps = window.itl_gaps(run.records, run.window)
    return 1e3 * window.percentile(gaps, 95) if gaps else None
