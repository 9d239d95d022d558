"""Percent of the traced window in which no operation ran on the device
(1 - the union of device-operation intervals over the window, from the
profiler trace), in the scoring cells."""

from chipbench.trace import idle_percent


def read(run):
    return idle_percent(run.trace_red)
