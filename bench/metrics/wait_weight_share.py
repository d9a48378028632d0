"""Share of the window in which the engine's main thread blocked on a
layer's weights (its ``wait.weight_load`` spans, host clock): the time
the weight tier held the pipeline up."""
from trace_reduce import clip, merge

KINDS = ("wait.weight_load",)


def read(run):
    # a program that spans no wait at all has nothing to read; one that
    # spans waits but never blocked on this producer reads 0
    if not any(e.kind.startswith("wait.") for e in run.host_events):
        return None
    spans = clip([(e.t_start, e.t_end) for e in run.host_events
                  if e.kind in KINDS], run.t0, run.t1)
    return 100.0 * sum(t - s for s, t in merge(spans)) / run.window_s
