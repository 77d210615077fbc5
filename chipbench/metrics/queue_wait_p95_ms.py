"""95th percentile of the queue wait of the requests delivered in the
window: from when a request was due to the admission that seated it.
That is the harness's wait from due to submit (an open loop submits what
fell due during a turn when the turn ends) plus the program's own from
submit to seat (``ImageRequest.queue_wait_s``, on the batcher's clock);
in a closed loop a request is due when it is submitted."""

from chipbench.stats import percentile


def read(ctx):
    waits = [r.submit - r.due + r.request.queue_wait_s
             for r in ctx.window["delivered"]
             if r.request.queue_wait_s is not None]
    return 1e3 * percentile(waits, 95) if waits else None
