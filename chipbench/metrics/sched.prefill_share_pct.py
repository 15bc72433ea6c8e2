"""Scheduler: wall time inside prefill-chunk calls over wall time inside
``step``, in the window (%, host clock)."""


def read(run):
    steps = run.calls_in_window("step")
    if not steps:
        return None
    pre = sum(c.t1 - c.t0 for c in run.calls_in_window("prefill"))
    return 100.0 * pre / sum(c.t1 - c.t0 for c in steps)
