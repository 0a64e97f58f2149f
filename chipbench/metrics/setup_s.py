"""Set-up: from process start to the first timed step or request (loading,
weights from the seed, compiling or loading every program, warm-up)."""


def read(ctx):
    return ctx["setup_s"]
