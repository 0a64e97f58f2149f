"""Share of the device's busy time (self time in the window) that remat
spends recomputing the forward pass: ops whose name stack holds
``rematted_computation``, in any layer."""

from chipbench import scopes


def read(ctx):
    return scopes.recompute_share(scopes.for_run(ctx))
