"""gemm.ms_per_step: device time of the gemm class of operations per train step
(classes as ``bench/trace.py`` sorts them)."""


def read(ctx):
    return ctx["trace"].ms_per_step("gemm")
