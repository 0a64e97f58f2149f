"""Model FLOP utilisation of the training step, for a configuration whose
plain reference gives its model FLOPs per token (``flops_per_token``:
forward and backward, no recomputation; the routed experts at the
assignments this chip's experts compute), times the tokens per second per
chip, over the chip's bf16 peak."""

from chipbench import program


def read(ctx):
    cfg = ctx["config"]
    fpt = program.reference(cfg).flops_per_token(cfg, ctx["mix"]["seq_len"])
    rec = ctx["records"]
    per_chip = rec["tokens"] / ctx["window_s"] / rec["chips"]
    return 100.0 * per_chip * fpt / ctx["peaks"]["bf16_flops"]
