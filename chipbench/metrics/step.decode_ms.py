"""Model step (jitted ``decode_paged`` at ``(slots, 1)``): total wall time
of the window's decode calls over their count (ms, host clock around work
that ends in ``block_until_ready``)."""


def read(run):
    calls = run.calls_in_window("decode")
    if not calls:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls)
