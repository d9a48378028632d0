"""Host-to-device KV rate over the link phases alone: the bytes of the KV
loads' ``kv_load.put`` phases (which carry them) over the merged seconds
of their ``put`` and ``ready`` phases (host clock).  Next to
``kv_h2d_gbps``, which spans whole loads, the difference is what the host
copy (``stage``) and the device-side pad (``pad``) cost.  Saves, the
other direction, are left out: their ``fetch`` also waits for device ops
the save itself dispatches."""
from trace_reduce import merge

KINDS = ("kv_load.put", "kv_load.ready")


def read(run):
    ev = [e for e in run.host_events if e.kind in KINDS]
    busy = sum(t - s for s, t in merge((e.t_start, e.t_end) for e in ev))
    nbytes = sum(e.nbytes for e in ev)
    return nbytes / busy / 1e9 if busy and nbytes else None
