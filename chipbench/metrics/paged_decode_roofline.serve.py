"""Roofline share of the Pallas paged decode kernel: the least time its
calls in the window could take (the bytes of the K/V pages, their
positions, and the query and output rows each call reads or writes, over
the HBM bandwidth; or its FLOPs over the peak, whichever is larger), over
the kernel's device time. Every decode step and every prefill chunk calls
it once per layer."""

from chipbench.serve_work import chunks, decode_positions, have_spans
from chipbench.trace_reduce import ops_matching

# The kernel as the device trace names it today: the ``tpu_custom_call``
# whose first operand is the scalar-prefetched page table (s32) and whose
# result is the (B, Hkv, rows, D) bf16 output.
PATTERN = (r"^%\S+ = bf16\[\d+,\d+,\d+,\d+\]\S* custom-call\(s32\["
           r".*custom_call_target=\"tpu_custom_call\"")


def call_bytes(cfg, page: int, keys: int, rows: int) -> int:
    """One layer's call for one sequence: ``keys`` cached tokens (whole
    pages), ``rows`` query tokens."""
    d, hq, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or d // hq
    pages = -(-keys // page)
    kv = pages * page * hkv * hd * 2 * 2  # K and V, bf16
    pos = pages * page * 4
    q_out = rows * hq * hd * 2 * 2
    return kv + pos + q_out


def call_flops(cfg, keys: int, rows: int) -> float:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // hq
    return 4 * rows * hq * hd * keys


def read(ctx):
    tr = ctx["trace"]
    spent = ops_matching(tr, PATTERN)
    if not spent or not have_spans(ctx):
        return None
    cfg = ctx["config"]
    page = cfg["serving"]["page_size"]
    L = cfg["num_hidden_layers"]
    nbytes = flops = 0.0
    for off, n in chunks(ctx):
        nbytes += call_bytes(cfg, page, off + n, n)
        flops += call_flops(cfg, off + n, n)
    for p in decode_positions(ctx):
        nbytes += call_bytes(cfg, page, p + 1, 1)
        flops += call_flops(cfg, p + 1, 1)
    least = L * max(nbytes / ctx["peaks"]["hbm_bytes_per_s"],
                    flops / ctx["peaks"]["bf16_flops"])
    return 100.0 * least / spent
