"""Roofline share of the Pallas flash-attention kernels in training
(forward, dq and dkv): the least time their calls could take on the chip
(each call's causal FLOPs over the bf16 peak or its bytes over the HBM
bandwidth, whichever is larger), over their summed device time."""

from chipbench.trace_reduce import calls_matching, ops_matching

# The kernels as the device trace names them today: Pallas calls carry no
# name, so each is the HLO text of a ``tpu_custom_call`` told apart by its
# results. The forward returns (o, lse) as (bf16, f32) over (B*H, S, D);
# dq returns one bf16 (B*H, S, D); dkv returns (dk, dv), two bf16.
_CALL = r" custom-call\(.*custom_call_target=\"tpu_custom_call\""
_T3 = r"\[\d+,\d+,\d+\]\S*"
KERNELS = {"fwd": rf"^%\S+ = \(bf16{_T3}, f32{_T3}\){_CALL}",
           "dq": rf"^%\S+ = bf16{_T3}{_CALL}",
           "dkv": rf"^%\S+ = \(bf16{_T3}, bf16{_T3}\){_CALL}"}
PATTERN = "|".join(KERNELS.values())
# Causal units (QK^T sized products over the causal pairs) per call:
# forward QK^T and PV; dq recomputes S and dP and forms dQ; dkv recomputes
# S and dP and forms dK and dV.
UNITS = {"fwd": 2, "dq": 3, "dkv": 4}


def call_cost(cfg, rows: int, seq: int, kind: str):
    """(FLOPs, bytes) of one call over ``rows`` rows of ``seq`` tokens."""
    d, hq, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or d // hq
    unit = 2 * rows * hq * hd * seq * (seq + 1) / 2
    q = rows * seq * hq * hd * 2  # bf16
    kv = rows * seq * hkv * hd * 2
    lse = rows * hq * seq * 4
    moved = {"fwd": 2 * q + 2 * kv + lse,          # q, k, v in; o, lse out
             "dq": 3 * q + 2 * kv + 2 * lse,       # q, do, k, v, lse, delta; dq
             "dkv": 2 * q + 4 * kv + 2 * lse}[kind]  # q, do, k, v, ...; dk, dv
    return UNITS[kind] * unit, moved


def read(ctx):
    tr = ctx["trace"]
    spent = ops_matching(tr, PATTERN)
    if not spent:
        return None
    cfg = ctx["config"]
    rows = cfg["global_batch"] // ctx["cell"]["chips"]
    least = 0.0
    for kind, pat in KERNELS.items():
        flops, moved = call_cost(cfg, rows, ctx["mix"]["seq_len"], kind)
        per_call = max(flops / ctx["peaks"]["bf16_flops"],
                       moved / ctx["peaks"]["hbm_bytes_per_s"])
        least += calls_matching(tr, pat) * per_call
    return 100.0 * least / spent
