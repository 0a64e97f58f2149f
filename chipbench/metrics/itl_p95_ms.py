"""95th percentile (nearest rank) of every gap between consecutive output
tokens whose later token came inside the window, of any request (pre-roll
ones included): the gaps the window's users saw."""

from chipbench.stats import nearest_rank


def values(ctx):
    rec = ctx["records"]
    lo, hi = rec["t_open"], rec["t_close"]
    out = []
    for r in rec["all_requests"]:
        t = r["times"]
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:]) if lo <= b < hi)
    return out


def read(ctx):
    v = values(ctx)
    return nearest_rank(v, 95) if v else None
