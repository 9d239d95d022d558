"""The on-chip benchmark's fixed yardstick: device checks, the table of
peaks, host spans, the profiler-trace reduction and the loader that finds
each configuration, traffic mix, generator, reference and metric by name.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own beside this package (``configs/``, ``traffic/``,
``generators/``, ``algos/``, ``refs/``, ``work/``, ``limits/``,
``metrics/``); nothing here names a cell.
"""
