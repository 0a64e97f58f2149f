"""Model FLOP utilisation of serving: the forward FLOPs of the prompt
tokens prefilled and the output tokens decoded in the window, over the
window and the chip's bf16 peak. A token at position p costs the layers'
projections and attention over p + 1 keys; the LM head counts only where
a token is chosen (decode steps and each prompt's last token)."""

from chipbench.serve_work import chunks, decode_positions, have_spans


def layer_params(cfg) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def token_flops(cfg, pos: int, head: bool) -> float:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // hq
    L = cfg["num_hidden_layers"]
    f = 2 * L * layer_params(cfg) + 4 * L * hq * hd * (pos + 1)
    return f + (2 * cfg["vocab_size"] * d if head else 0)


def chunk_flops(cfg, off: int, n: int, last: bool) -> float:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // hq
    L = cfg["num_hidden_layers"]
    keys = n * off + n * (n + 1) / 2  # sum of (p + 1) over the chunk
    f = 2 * L * layer_params(cfg) * n + 4 * L * hq * hd * keys
    return f + (2 * cfg["vocab_size"] * d if last else 0)


def read(ctx):
    if not have_spans(ctx):
        return None
    cfg = ctx["config"]
    flops = sum(chunk_flops(cfg, off, n, False) for off, n in chunks(ctx))
    flops += sum(token_flops(cfg, p, True) for p in decode_positions(ctx))
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["bf16_flops"]
