"""Output tokens emitted inside the window (by any request, pre-roll
included), over the window."""


def read(ctx):
    rec = ctx["records"]
    lo, hi = rec["t_open"], rec["t_close"]
    n = sum(1 for r in rec["all_requests"] for t in r["times"] if lo <= t < hi)
    return n / ctx["window_s"]
