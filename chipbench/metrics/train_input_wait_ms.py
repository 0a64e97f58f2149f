"""Mean time per step the loop waits for its next batch on the device
(rows made and transferred), on the host clock."""


def read(ctx):
    waits = ctx["records"]["input_s"]
    return 1e3 * sum(waits) / len(waits)
