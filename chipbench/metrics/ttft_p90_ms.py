"""90th percentile, over every request due in the window, of first-token
time minus the time the request was due; a request that failed or has no
first token counts as a miss at the top of the tail (ms, host clock)."""
import math

from chipbench import window


def read(run):
    t = window.ttfts(run.records, run.window)
    if not t:
        return None
    v = window.percentile(t, 90)
    return 1e3 * v if math.isfinite(v) else 1e9
