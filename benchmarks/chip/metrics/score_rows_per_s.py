"""Rows whose scores reached the host inside the window, over the
window's length."""


def read(run):
    w = run.window
    if "rows_done" not in w:
        return None
    return w["rows_done"] / w["seconds"]
