"""Device (TPU v5e): one minus the union of device-op intervals over the
union of ``step`` spans, in the traced stretch (%, device trace)."""
from chipbench import trace


def read(run):
    if run.trace is None:
        return None
    return 100.0 * trace.idle_in_ticks(run.trace)
