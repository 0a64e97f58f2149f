"""Share of the window in which no operation ran on the device (averaged
over the chips)."""


def read(ctx):
    share = ctx["trace"]["idle_share"]
    return None if share is None else 100.0 * share
