"""KV bytes moved each way (KV_LOAD + KV_SAVE) per token emitted in the
window, in MB."""


def read(run):
    n = len(run.tokens_in_window())
    b = sum(e.nbytes for e in run.host_events
            if e.kind in ("kv_load", "kv_save"))
    return b / n / 1e6 if n and b else None
