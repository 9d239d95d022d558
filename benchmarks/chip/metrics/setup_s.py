"""Set-up seconds: process start to the start of the measured window
(JAX start-up, data made on the device, planning, compiles or cache
loads, and the warm-up of the cell's own shapes)."""


def read(run):
    return run.setup_s
