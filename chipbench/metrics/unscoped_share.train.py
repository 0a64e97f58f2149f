"""Share of the device's busy time (self time in the window) in ops under
no program scope: what the module scopes leave unattributed (ops XLA makes
without a name stack, such as casts and fills it adds on its own)."""

from chipbench import scopes


def read(ctx):
    split = scopes.split(scopes.for_run(ctx))
    return None if split is None else split["unscoped"]
