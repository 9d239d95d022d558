"""Mean requests per dispatched batch over the window, from the
server's own counters (``FusionServer.metrics.snapshot()`` before and
after the window)."""


def read(run):
    return run.window.get("occupancy")
