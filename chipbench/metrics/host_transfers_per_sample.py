"""Device-to-host reads the serve loop issued in the window (the
program's ``serve_host_transfers_total``), per sample delivered in it."""


def read(ctx):
    n = len(ctx.window["delivered"])
    return ctx.window["transfers"] / n if n else None
