"""Share of the device's busy time (self time in the window) in the expert
layers' routing: ops under the ``router``, ``moe.dispatch`` (sort and
gather into expert order) and ``moe.combine`` (the weighted sum back in
token order) scopes, forward, recomputation and backward."""

from chipbench import scopes

ROUTING = ("router", "moe.dispatch", "moe.combine")


def read(ctx):
    reduced = scopes.for_run(ctx)
    if not reduced["self_s"]:
        return None
    spent = sum(r["self_s"] for r in reduced["ops"].values()
                if set(ROUTING) & set(scopes.module_path(r["op_name"])))
    return 100.0 * spent / reduced["self_s"] if spent else None
