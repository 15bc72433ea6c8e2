"""Kernels (the compiled decode program): the least time the chip needs
for the traced decode calls' necessary work, over the device time of the
trace's operations inside their spans (%, device trace).

Necessary work (the family's ``call_cost``): every weight read once, the
keys and values of live tokens, the state of active rows; two FLOPs per
weight per row and attention over live lengths.  Never the padded table
width, so a program that stops gathering it raises this share."""
from chipbench import trace
from chipbench.peaks import least_seconds


def read(run):
    if run.peaks is None:   # no chip, no device numbers
        return None
    if run.trace is None:
        return None
    dev_ns = trace.device_ns_by_call(run.trace, "decode")
    calls = {c.index: c for c in run.calls if c.kind == "decode"}
    least, busy = 0.0, 0.0
    for idx, ns in dev_ns.items():
        rows = calls[idx].rows()
        if ns <= 0 or not rows:
            continue
        flops, nbytes = run.family.call_cost(run.sizes, rows, len(rows))
        least += least_seconds(flops, nbytes, run.peaks)
        busy += ns / 1e9
    return 100.0 * least / busy if busy else None
