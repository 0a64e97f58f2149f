"""Share of the device's busy time (self time in the window) in ops under
the program's ``self_attention`` scopes: the q/k/v/o projections, RoPE and
the flash-attention kernels, forward, recomputation and backward."""

from chipbench import scopes


def read(ctx):
    split = scopes.split(scopes.for_run(ctx))
    return None if split is None else split["attention"]
