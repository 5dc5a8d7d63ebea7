"""conv.ms_per_step: device time per train step of the ops in the
program's ``conv`` scope: XLA's convolutions, forward and both gradients,
with whatever XLA fuses into their roots (batch norm's and ReLU's
elementwise work, the weights' SGD update).  Scopes as
``layers.scope_of`` reads them from the compiled step's metadata."""


def read(ctx):
    red = ctx["trace"]
    return red.per_step(red.scope_s.get("conv", 0.0))
