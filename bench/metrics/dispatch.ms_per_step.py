"""dispatch.ms_per_step: host time of the dispatch call per train step, the
union of JAX's ``PjitFunction`` events inside the benchmark's
``bench.dispatch`` spans (``layers.dispatch_split``)."""


def read(ctx):
    red = ctx["trace"]
    return red.per_step(red.dispatch_s)
