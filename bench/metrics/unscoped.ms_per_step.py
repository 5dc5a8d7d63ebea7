"""unscoped.ms_per_step: device time per train step of the ops that
carry no program scope: XLA's copies and relayouts, and any op whose
metadata names no scope.  Scopes as ``layers.scope_of`` reads them from
the compiled step's metadata."""


def read(ctx):
    red = ctx["trace"]
    return red.per_step(red.scope_s.get("unscoped", 0.0))
