"""Plan builds the server counted inside the window (its ``compiles``
counter, before and after the window); every row class is warmed in
set-up, so this should read 0."""


def read(run):
    v = run.window.get("server_compiles")
    return None if v is None else float(v)
