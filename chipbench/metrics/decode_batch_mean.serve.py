"""Mean batch of the fused decode step in the window: output tokens from
decode steps over the scheduler's ``decode_steps`` counter."""

from chipbench.serve_work import decode_positions


def read(ctx):
    rec = ctx["records"]
    steps = rec["stats1"]["decode_steps"] - rec["stats0"]["decode_steps"]
    if steps <= 0:
        return None
    return sum(1 for _ in decode_positions(ctx)) / steps
