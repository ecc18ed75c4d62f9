"""Device layer: share of the traced window in which no operation ran on
the device (1 - union of op intervals / window)."""


def read(window):
    if window.trace is None:
        return None
    return 100.0 * window.trace.idle_share
