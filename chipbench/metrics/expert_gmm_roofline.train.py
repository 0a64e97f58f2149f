"""Roofline share of the grouped matrix products of the expert layers
(every op under the ``expert_gmm`` scope, forward and backward): the least
time of their kernel calls over the ops' device time.

Each grouped-product kernel call (a ``pallas_call`` under the scope: the
forward product, the input gradient, the weight gradient) multiplies one
sequence's held rows of one layer by the held experts' (d, f) matrices:
2 N d f FLOPs, and at least the held experts' weights and the N rows in and
out in bf16. N is the step's held assignments (the program's counter) over
the layers and the rows of a step."""

from chipbench import scopes


def read(ctx):
    held = ctx["records"].get("held_assignments")
    if not held:
        return None
    reduced = scopes.for_run(ctx)
    spent = calls = 0.0
    for r in reduced["ops"].values():
        name = r["op_name"] or ""
        if "expert_gmm" in scopes.module_path(name):
            spent += r["self_s"]
            if name.endswith("pallas_call"):
                calls += r["calls"]
    if not spent:
        return None
    cfg, peaks = ctx["config"], ctx["peaks"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = cfg["global_batch"] // ctx["cell"]["chips"]
    per_step = sum(sum(layers) for layers in held) / len(held)
    n = per_step / (len(held[0]) * rows)
    flops = 2 * n * d * f
    moved = 2 * (cfg["n_routed_experts"] * d * f + n * (d + f))
    least = calls * max(flops / peaks["bf16_flops"],
                        moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
