"""Model FLOP utilisation of the training step: model FLOPs per token from
the shapes (forward and backward, no recomputation), times the tokens per
second per chip, over the chip's bf16 peak."""


def matmul_params(cfg) -> int:
    """Weights that enter a matrix product, per token: every layer's
    projections and the (tied) LM head; not the embedding lookup."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return L * layer + cfg["vocab_size"] * d


def attention_flops_per_token(cfg, seq: int) -> float:
    """Causal attention forward, per token of a ``seq``-token row: QK^T and
    PV over the (seq + 1) / 2 keys a token sees on average."""
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // hq
    return cfg["num_hidden_layers"] * 2 * hq * hd * (seq + 1)


def flops_per_token(cfg, seq: int) -> float:
    """Training FLOPs per token: three times the forward pass."""
    return 3 * (2 * matmul_params(cfg) + attention_flops_per_token(cfg, seq))


def read(ctx):
    rec = ctx["records"]
    per_chip = rec["tokens"] / ctx["window_s"] / rec["chips"]
    fpt = flops_per_token(ctx["config"], ctx["mix"]["seq_len"])
    return 100.0 * per_chip * fpt / ctx["peaks"]["bf16_flops"]
