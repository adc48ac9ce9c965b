"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
