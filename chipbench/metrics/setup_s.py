"""From process start to the start of traffic: imports, chip start-up,
weights from the seed, compile or cache load, warm-up (s, host clock)."""


def read(run):
    return run.setup_s
