"""Share of the device's busy time (self time in the window) in the
optimizer update: ops under the ``learner`` scope (clipping, AdamW, the
new parameters and the step's norm telemetry)."""

from chipbench import scopes


def read(ctx):
    split = scopes.split(scopes.for_run(ctx))
    return None if split is None else split["learner"]
