"""Share of the window in which the engine's main thread ran no layer
compute (its COMPUTE spans, host clock): the scheduler's wait for
transfers and host code.  Not the device's idle share."""
from trace_reduce import merge


def read(run):
    comp = [(max(e.t_start, run.t0), min(e.t_end, run.t1))
            for e in run.host_events if e.kind == "compute"]
    if not comp:
        return None
    busy = sum(t - s for s, t in merge(comp))
    return 100.0 * (1.0 - busy / run.window_s)
