"""bn.ms_per_step: device time per train step of the ops in the
program's ``bn`` scope: the batch-norm work that XLA did not fuse into a
convolution.  Scopes as ``layers.scope_of`` reads them from the compiled
step's metadata."""


def read(ctx):
    red = ctx["trace"]
    return red.per_step(red.scope_s.get("bn", 0.0))
