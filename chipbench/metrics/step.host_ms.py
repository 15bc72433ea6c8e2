"""Engine tick (``PagedServeEngine.step`` outside its model calls): the
median over the window's ``step`` calls of their wall time less that of
the prefill and decode calls inside them (ms, host clock).  A median, so
that the host stall when the profiler starts does not set it."""
import statistics


def read(run):
    steps = run.calls_in_window("step")
    if not steps:
        return None
    model = sorted((c.t0, c.t1) for c in run.calls
                   if c.kind != "step" and run.window.holds(c.t0))
    host = []
    for s in steps:
        inner = sum(b - a for a, b in model if s.t0 <= a and b <= s.t1)
        host.append(s.t1 - s.t0 - inner)
    return 1e3 * statistics.median(host)
