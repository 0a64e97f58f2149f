"""The window's mean, over steps and expert layers, of the largest held
expert's load over the held experts' mean load (the program's counter
``held_load_max_over_mean``; 1 is even)."""


def read(ctx):
    steps = ctx["records"].get("held_load_max_over_mean")
    if not steps:
        return None
    values = [x for layers in steps for x in layers]
    return sum(values) / len(values)
