"""xla.ms_per_step: device time of the xla class of operations per train step
(classes as ``bench/trace.py`` sorts them)."""


def read(ctx):
    return ctx["trace"].ms_per_step("xla")
