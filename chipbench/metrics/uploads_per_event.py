"""Host-to-device uploads of the device-resident serve loop per call of
its event program, over the window: the deltas of the program's
``serve_host_uploads_total`` and ``serve_event_updates_total``. A visit
that calls the event program uploads its admission (and, tiered, the
tolerance rows) and the occupancy mask; more per event means uploads per
request came back."""


def read(ctx):
    c = ctx.window["counters"]
    events = c.get("serve_event_updates_total", 0)
    return c.get("serve_host_uploads_total", 0) / events if events else None
