"""Weight bytes streamed per token emitted in the window, in MB: a count
that repeats run to run for the same work."""


def read(run):
    n = len(run.tokens_in_window())
    b = sum(e.nbytes for e in run.host_events if e.kind == "weight_load")
    return b / n / 1e6 if n else None
