"""Device: the share of the traced window in which no operation ran on the
device, averaged over the cell's chips."""


def read(view):
    if view.trace is None:
        return None
    return 100.0 * (1.0 - view.trace.busy_s / view.trace.window_s)
