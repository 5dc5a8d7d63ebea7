"""optimizer.ms_per_step: device time per train step of the ops in the
program's ``optimizer`` scope: the update work that XLA did not fuse into
a weight-gradient convolution.  Scopes as ``layers.scope_of`` reads them
from the compiled step's metadata."""


def read(ctx):
    red = ctx["trace"]
    return red.per_step(red.scope_s.get("optimizer", 0.0))
