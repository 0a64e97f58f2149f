"""Prefill dispatches per admitted request in the window: the scheduler's
``prefill_chunks`` over its ``admitted`` counter."""


def read(ctx):
    s0, s1 = ctx["records"]["stats0"], ctx["records"]["stats1"]
    admitted = s1["admitted"] - s0["admitted"]
    if admitted <= 0:
        return None
    return (s1["prefill_chunks"] - s0["prefill_chunks"]) / admitted
