"""Tokens of every step completed in the window, over the window (which
opens and closes on a ``block_until_ready``), over the chips."""


def read(ctx):
    rec = ctx["records"]
    return rec["tokens"] / ctx["window_s"] / rec["chips"]
