"""Process start to the window's first step: imports, engine build and
weights, compilation or its cache, warm-up and the fill the traffic
needs (host clock)."""


def read(run):
    return run.setup_s
