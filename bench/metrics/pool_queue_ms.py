"""Mean time a transfer task sat in the pool's queue, from its submit to
a worker taking it (``queue.*`` spans of the weight loads, KV loads and
KV saves that ended in the window; host clock), in ms."""

KINDS = ("queue.weight_load", "queue.kv_load", "queue.kv_save")


def read(run):
    d = [e.t_end - e.t_start for e in run.host_events if e.kind in KINDS]
    return 1e3 * sum(d) / len(d) if d else None
