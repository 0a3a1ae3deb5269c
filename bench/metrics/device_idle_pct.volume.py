"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of device-op intervals / window), averaged over chips."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
