"""Share of the traced slice that the serve loop spent in its host
visits between device programs: the summed time of the ``serve/event``
spans (device-resident loop: retire, compact, admit) and ``serve/sync``
spans (host-driven loop), cut to the slice, over the slice."""

VISITS = ("serve/event", "serve/sync")


def read(ctx):
    spent = sum(e - s for s, e, name in ctx.spans if name in VISITS)
    window = ctx.trace["window_s"]
    return 100.0 * spent / 1e9 / window if spent and window else None
