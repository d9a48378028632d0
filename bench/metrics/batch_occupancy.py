"""Rows per decode step: tokens emitted in the window over decode steps
run in it (the engine's own counters)."""


def read(run):
    steps = run.delta("decode_steps")
    return run.delta("tokens_out") / steps if steps else None
