"""Model step (jitted ``decode_paged`` at ``(1, chunk)``): total wall time
of the window's prefill-chunk calls over their count (ms, host clock)."""


def read(run):
    calls = run.calls_in_window("prefill")
    if not calls:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls)
