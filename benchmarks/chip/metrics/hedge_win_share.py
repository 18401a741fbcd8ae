"""Hedge tier: share of the requests due in the window that the on-device
duplicate answered (those clear of the profiler's start and stop)."""


def read(view):
    if not view.clean:
        return None
    won = sum(r.race == "ondevice_won" for r in view.clean)
    return 100.0 * won / len(view.clean)
