"""Host seconds of set-up spent planning the cell's fused regions
(trace -> rewrite -> explore -> select -> verify, forward and planned
backward), summed by the benchmark's ``bench.plan`` span."""


def read(run):
    return run.spans.seconds.get("bench.plan")
