"""Share of the traced window in which the chip ran no operation: 1 minus
the union of the device's op intervals over the window (profiler trace,
averaged over the chips used)."""


def read(run):
    return None if run.device is None else 100.0 * run.device.idle_share()
