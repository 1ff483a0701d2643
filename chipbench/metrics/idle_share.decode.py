"""Share of the traced window in which no operation ran on the chip, in
percent: 1 - (union of device-op intervals / window)."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
