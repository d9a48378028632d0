"""Share of the window the engine spent prefilling admitted requests
(its ``engine.prefill`` spans, one per admission, merged; host clock)."""
from trace_reduce import clip, merge

KINDS = ("engine.prefill",)


def read(run):
    spans = clip([(e.t_start, e.t_end) for e in run.host_events
                  if e.kind in KINDS], run.t0, run.t1)
    if not spans:
        return None
    return 100.0 * sum(t - s for s, t in merge(spans)) / run.window_s
