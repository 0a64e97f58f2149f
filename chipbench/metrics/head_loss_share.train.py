"""Share of the device's busy time (self time in the window) in the LM head
and the cross-entropy: ops under the head's scope (``emb.attend`` for the
tied head) or the ``loss`` scope, forward and backward."""

from chipbench import scopes


def read(ctx):
    split = scopes.split(scopes.for_run(ctx))
    return None if split is None else split["head_loss"]
