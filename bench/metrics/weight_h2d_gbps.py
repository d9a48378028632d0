"""Host-to-device weight rate achieved: WEIGHT_LOAD bytes in the window
over the merged busy seconds of those loads (host clock; each load
blocks until its arrays are on the device)."""
from trace_reduce import merge


def read(run):
    ev = [e for e in run.host_events if e.kind == "weight_load" and e.nbytes]
    busy = sum(t - s for s, t in merge((e.t_start, e.t_end) for e in ev))
    return sum(e.nbytes for e in ev) / busy / 1e9 if busy else None
