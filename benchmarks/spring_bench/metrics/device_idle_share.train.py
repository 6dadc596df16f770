"""Percent of the traced window in which no op ran on the device:
1 - (union of device-op intervals / window)."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * run.trace.idle_share()
