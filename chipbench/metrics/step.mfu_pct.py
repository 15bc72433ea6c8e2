"""Whole step against the chip's peak: the useful model FLOPs of every
token the window's calls processed (prefill and decode rows, attention
over live context), over the window's ``step`` wall time times the peak
bf16 FLOP/s (%, counter over host clock)."""


def read(run):
    if run.peaks is None:   # no chip, no device numbers
        return None
    steps = run.calls_in_window("step")
    if not steps:
        return None
    flops = 0.0
    for kind in ("prefill", "decode"):
        for c in run.calls_in_window(kind):
            rows = c.rows()
            f, _ = run.family.call_cost(run.sizes, rows,
                                        len(rows) if kind == "decode" else 0)
            flops += f
    wall = sum(c.t1 - c.t0 for c in steps)
    return 100.0 * flops / (wall * run.peaks["bf16_flops"])
