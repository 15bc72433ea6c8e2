"""Every generated token emitted inside the window, over the window's
length (tokens/s, host clock)."""
from chipbench import window


def read(run):
    return window.output_tok_s(run.records, run.window)
