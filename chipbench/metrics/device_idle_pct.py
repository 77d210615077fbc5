"""Share of the traced slice in which no operation ran on the chip:
1 - the union of the device's operation intervals over the slice."""


def read(ctx):
    window = ctx.trace["window_s"]
    return 100.0 * (1.0 - ctx.trace["busy_s"] / window) if window else None
