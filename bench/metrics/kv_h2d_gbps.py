"""Host-to-device KV rate achieved: KV_LOAD bytes in the window over the
merged busy seconds of those loads (host clock)."""
from trace_reduce import merge


def read(run):
    ev = [e for e in run.host_events if e.kind == "kv_load" and e.nbytes]
    busy = sum(t - s for s, t in merge((e.t_start, e.t_end) for e in ev))
    return sum(e.nbytes for e in ev) / busy / 1e9 if busy else None
