"""Front end (``serve.server.AsyncServeFrontend``): 95th percentile over
the window's requests of the time from when a request was due to when
``submit`` returned (ms, host clock).  The task that steps the engine
blocks the loop for a whole tick, so a request due mid-tick waits."""
from chipbench import window


def read(run):
    lags = window.submit_lags(run.records, run.window)
    return 1e3 * window.percentile(lags, 95) if lags else None
