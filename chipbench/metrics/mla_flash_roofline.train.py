"""Roofline share of the Pallas flash-attention kernels (forward, dq, dkv)
in a training cell whose q/k and v head dims differ (latent attention:
q/k ``qk_nope_head_dim + qk_rope_head_dim``, v ``v_head_dim``): the least
time their calls could take on the chip (each call's causal FLOPs over the
bf16 peak or its bytes over the HBM bandwidth, whichever is larger), over
their device time. A kernel is the ``pallas_call`` under its scope
(``flash_fwd``, ``flash_dq``, ``flash_dkv``)."""

from chipbench import scopes

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def call_cost(cfg, rows: int, seq: int, kind: str):
    """(FLOPs, bytes) of one call over ``rows`` rows of ``seq`` tokens.
    Per causal (query, key) pair: the forward forms QK^T (q/k dim) and PV
    (v dim); dq recomputes S and dP and forms dQ (q/k dim); dkv recomputes
    S and dP and forms dV (v dim) and dK (q/k dim). Bytes: bf16 q, k, v, o
    (or dO) and outputs, f32 per-row statistics."""
    h = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = rows * h * seq * (seq + 1) / 2
    dims = {"flash_fwd": dqk + dv, "flash_dq": 2 * dqk + dv,
            "flash_dkv": 2 * dqk + 2 * dv}[kind]
    qk = rows * seq * h * dqk * 2
    v = rows * seq * h * dv * 2
    stat = rows * h * seq * 4
    moved = {"flash_fwd": 2 * qk + 2 * v + stat,       # q, k, v; o, lse
             "flash_dq": 3 * qk + 2 * v + 2 * stat,    # q, k, v, dO, lse, delta; dq
             "flash_dkv": 3 * qk + 3 * v + 2 * stat}[kind]  # ...; dk, dv
    return 2 * pairs * dims, moved


def kernel_time(reduced, scope: str):
    """(seconds, calls) of the ``pallas_call`` ops under ``scope``."""
    spent = calls = 0.0
    for r in reduced["ops"].values():
        name = r["op_name"] or ""
        if name.endswith("pallas_call") and scope in scopes.module_path(name):
            spent += r["self_s"]
            calls += r["calls"]
    return spent, calls


def read(ctx):
    reduced = scopes.for_run(ctx)
    cfg, peaks = ctx["config"], ctx["peaks"]
    rows = cfg["global_batch"] // ctx["cell"]["chips"]
    spent = least = 0.0
    for kind in KERNELS:
        s, calls = kernel_time(reduced, kind)
        flops, moved = call_cost(cfg, rows, ctx["mix"]["seq_len"], kind)
        spent += s
        least += calls * max(flops / peaks["bf16_flops"],
                             moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent if spent else None
