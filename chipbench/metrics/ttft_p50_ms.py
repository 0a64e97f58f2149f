"""Median (nearest rank) of time to first token over every request due in
the window, timed from its due time; a request with no first token counts
as infinite. A window holds about 14 requests at this cell's rate, so the
median is the highest percentile with samples beyond it to rest on."""

from chipbench.stats import nearest_rank


def values(ctx):
    return [(r["times"][0] - r["due"]) * 1e3 if r["times"] else float("inf")
            for r in ctx["records"]["requests"]]


def read(ctx):
    return nearest_rank(values(ctx), 50)
