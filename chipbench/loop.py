"""Drives the program's server with one traffic mix and times it.

One thread: submit what is due, take one serve-loop turn
(``DiffusionBatcher.step``), stamp what it delivered, repeat. A request is
timed from when it was due (open loop) or submitted (closed loop) to the
end of the turn that delivered it. The window is ``seconds`` long; after
it closes the traffic goes on, so the window's last requests see the same
load, until each of them is delivered or ``max_wait_s`` has passed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

#: uids of warm-up requests start here, far above any traffic index
WARM_UID = 1 << 40


@dataclasses.dataclass
class Record:
    spec: Dict[str, Any]
    client: Optional[int]
    due: float
    submit: float = math.nan
    delivered: Optional[float] = None
    nfe: int = 0
    result: Any = None
    #: the program's request object, once delivered
    request: Any = None
    #: the slot that seated it (each chip holds a contiguous block)
    slot: Optional[int] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.delivered is None else self.delivered - self.due


@dataclasses.dataclass
class Outcome:
    population: List[Record]  # due in the window
    t0: float
    t1: float
    delivered_in_window: List[Record]
    counters: Dict[str, int]  # deltas over the window, as ``counters`` keys them
    lateness: List[float]
    #: due before the window closed and never delivered
    undelivered: int
    #: when the run stopped waiting
    t_end: float


#: the serve loop's four counters under the benchmark's own short names
LEGACY = ("iterations", "useful_nfe", "resident_nfe", "transfers")


def counters(b) -> Dict[str, int]:
    """The batcher's counters now: the four of ``LEGACY``, and every
    counter series of its metrics registry under the registry's name
    (``name`` or, labelled, ``name{k="v",...}``)."""
    return {"iterations": b.total_iterations, "useful_nfe": b.useful_nfe,
            "resident_nfe": b.resident_nfe, "transfers": b.host_transfers,
            **b.metrics.to_json()["counters"]}


def deltas(start: Dict[str, int], end: Dict[str, int]) -> Dict[str, int]:
    """``end - start`` for every key of ``end`` (a labelled series that
    first appeared in between started at 0)."""
    return {k: v - start.get(k, 0) for k, v in end.items()}


def warm_up(server, limit_s: float) -> bool:
    """Compile every shape the serve loop's host code makes from how many
    requests one turn seats or delivers: groups of 1..slots requests that
    share one trajectory, so each group is seated and delivered whole,
    each submitted as soon as the slots can hold it; then the server's
    ``warm_tail`` requests. Returns False if ``limit_s`` passed without a
    warm-up request delivered."""
    b = server.batcher
    uid = WARM_UID
    groups = [[server.warm_request(uid + sum(range(k)) + j) for j in range(k)]
              for k in range(1, b.n + 1)]
    groups.append(server.warm_tail(WARM_UID // 2))
    submitted, delivered = 0, 0
    progress = time.perf_counter()
    while True:
        done = sum(1 for u in b.finished if u >= WARM_UID // 2)
        if done > delivered:
            delivered, progress = done, time.perf_counter()
        if not groups and delivered == submitted:
            return True
        if time.perf_counter() > progress + limit_s:
            return False
        if groups and submitted - delivered + len(groups[0]) <= b.n:
            for req in groups.pop(0):
                b.submit(req)
                submitted += 1
        b.step()


class TraceSlice:
    """Profiles ``length_s`` seconds that end with the window, starting
    and stopping at turn boundaries (the device is idle there), and
    counts the solver iterations and useful NFE run inside, and the
    deltas of the batcher's ``counters``."""

    def __init__(self, server, log_dir: str, length_s: float,
                 chrome: bool = False):
        self.server, self.log_dir, self.length_s = server, log_dir, length_s
        self.chrome = chrome
        self.state = "waiting"
        self.nfe0: Dict[int, int] = {}
        self.delivered: Dict[int, int] = {}

    def _iterations(self) -> int:
        b = self.server.batcher
        it = b.total_iterations
        if self.server.device_resident:
            # the device-resident loop folds its on-device counter into
            # the total only at events
            it += int(np.asarray(b._carry.iterations))
        return it

    def _inflight_nfe(self) -> Dict[int, int]:
        b = self.server.batcher
        nfe = np.asarray(b._carry.nfe)
        return {r.uid: int(nfe[i]) for i, r in enumerate(b._slot_req)
                if r is not None}

    def tick(self, now: float, t1: Optional[float]) -> None:
        import jax

        if self.state == "waiting" and t1 is not None \
                and now >= t1 - self.length_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts,
                                     create_perfetto_trace=self.chrome)
            self.ann = jax.profiler.TraceAnnotation("bench/trace")
            self.ann.__enter__()
            self.it0 = self._iterations()
            self.nfe0 = self._inflight_nfe()
            self.c0 = counters(self.server.batcher)
            self.state = "on"
        elif self.state == "on" and now >= t1:
            self.stop()

    def on_delivered(self, uid: int, nfe: int) -> None:
        if self.state == "on":
            self.delivered[uid] = nfe

    def stop(self) -> None:
        import jax

        if self.state != "on":
            return
        self.iterations = self._iterations() - self.it0
        self.counters = deltas(self.c0, counters(self.server.batcher))
        nfe1 = self._inflight_nfe()
        self.useful_nfe = (
            sum(n - self.nfe0.get(u, 0) for u, n in self.delivered.items())
            + sum(n - self.nfe0.get(u, 0) for u, n in nfe1.items()))
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def serve(server, traffic, seconds: float, *, max_wait_s: float,
          trace: Optional[TraceSlice] = None, uid_base: int = 0) -> Outcome:
    """Serve ``traffic`` for a window of ``seconds``; request ``i`` of the
    mix gets the uid ``uid_base + i``."""
    b = server.batcher
    seats: Dict[int, int] = {}
    admit = b._admit_from_queue

    def seat():
        # note the slot of each request the batcher seats
        pos, reqs = admit()
        for i, req in zip(pos, reqs):
            seats[req.uid] = i
        return pos, reqs

    b._admit_from_queue = seat
    if trace is not None:
        import jax

        ann = jax.profiler.TraceAnnotation
    else:
        ann = lambda name: contextlib.nullcontext()  # noqa: E731
    clock = time.perf_counter
    recs: Dict[int, Record] = {}
    seen = len(b.finished)
    open_loop = traffic.loop == "open"
    t_start = clock()
    t0 = t_start + traffic.window_start if open_loop else None
    t1 = None if t0 is None else t0 + seconds
    c_start = c_end = None
    lateness: List[float] = []
    delivered_total = 0
    preroll = 0 if open_loop else traffic.clients * int(
        traffic.p.get("preroll_per_client", 1))
    nxt = 0  # open loop: next request index to submit

    def submit(i: int, client: Optional[int], due: float) -> None:
        rec = Record(spec=traffic.spec(i), client=client, due=due)
        req = server.request(uid_base + i, rec.spec)
        rec.submit = clock()
        if not open_loop:
            rec.due = rec.submit
        recs[uid_base + i] = rec
        b.submit(req)

    if not open_loop:
        with ann("bench/submit"):
            for c in range(traffic.clients):
                submit(traffic.client_request(c, 0), c, 0.0)
        next_k = [1] * traffic.clients

    while True:
        now = clock()
        if open_loop:
            with ann("bench/submit"):
                while t_start + traffic.due(nxt) <= now:
                    submit(nxt, None, t_start + traffic.due(nxt))
                    rec = recs[uid_base + nxt]
                    lateness.append(rec.submit - rec.due)
                    nxt += 1
        elif t0 is None and (delivered_total >= preroll
                             or now > t_start + max_wait_s):
            t0, t1 = now, now + seconds
        if c_start is None and t0 is not None and now >= t0:
            c_start = counters(b)
        if trace is not None:
            trace.tick(now, t1)
        if c_end is None and t1 is not None and now >= t1:
            c_end = counters(b)
        if c_end is not None:
            waiting = [r for r in recs.values()
                       if r.due < t1 and r.delivered is None]
            if not waiting or now > t1 + max_wait_s:
                break
        with ann("bench/step"):
            busy = b.step()
        done_at = clock()
        with ann("bench/deliver"):
            if len(b.finished) > seen:
                new = list(b.finished.values())[seen:]
                seen = len(b.finished)
                for req in new:
                    rec = recs.get(req.uid)
                    if rec is None:  # left over from the warm-up
                        continue
                    rec.delivered, rec.nfe, rec.result = done_at, req.nfe, req.result
                    rec.request, rec.slot = req, seats.get(req.uid)
                    delivered_total += 1
                    if trace is not None:
                        trace.on_delivered(req.uid, req.nfe)
                    if not open_loop:
                        k = next_k[rec.client]
                        next_k[rec.client] += 1
                        submit(traffic.client_request(rec.client, k),
                               rec.client, 0.0)
        if busy == 0 and not b.queue and open_loop:
            with ann("bench/sleep"):
                time.sleep(max(0.0, t_start + traffic.due(nxt) - clock()))
    if trace is not None:
        trace.stop()
    b._admit_from_queue = admit
    population = [r for r in recs.values() if t0 <= r.due < t1]
    in_window = [r for r in recs.values()
                 if r.delivered is not None and t0 <= r.delivered < t1]
    return Outcome(
        population=population, t0=t0, t1=t1, delivered_in_window=in_window,
        counters=deltas(c_start, c_end),
        lateness=lateness,
        undelivered=sum(1 for r in recs.values()
                        if r.due < t1 and r.delivered is None),
        t_end=clock())
