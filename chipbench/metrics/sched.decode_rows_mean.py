"""Scheduler (``FifoScheduler`` through ``PagedServeEngine.step``): rows
decoding per decode call, averaged over the window's calls (counter)."""


def read(run):
    calls = run.calls_in_window("decode")
    if not calls:
        return None
    return sum(len(c.rows()) for c in calls) / len(calls)
