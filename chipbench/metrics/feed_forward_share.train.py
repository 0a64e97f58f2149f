"""Share of the device's busy time (self time in the window) in ops under
the program's ``feed_forward`` scopes: the SwiGLU projections and their
activation, forward, recomputation and backward."""

from chipbench import scopes


def read(ctx):
    split = scopes.split(scopes.for_run(ctx))
    return None if split is None else split["feed_forward"]
