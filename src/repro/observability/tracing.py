"""Serve-loop span tracing (DESIGN.md §15): spans over every host visit
of the batcher between device programs, and over the planner's rounds
and request construction.

Each span is recorded twice, on two clocks: as a ``{name, id, parent,
start, end, duration_s, attrs}`` dict on the tracer's injectable
monotonic clock, and as a ``jax.profiler.TraceAnnotation`` under its
bare name, so that with a profiler running it lands in the trace's
host plane on the device's clock, beside the programs it dispatched
(without one the annotation is a cheap no-op). ``parent`` is the id of
the span open around it, so a stage's self time is its duration less
its children's. The interesting structure (request-id propagation
through compaction, per-stage latency distributions) lives in the
*attrs* the serve loop attaches. ``NULL_TRACER`` is the default no-op:
its ``span`` yields without recording, so an untraced batcher reads no
clock, opens no annotation and records nothing.
"""

from __future__ import annotations

import bisect
import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

import jax

#: log-spaced latency bucket upper bounds (seconds) for the per-stage
#: histograms; the final implicit bucket is +Inf
LATENCY_BUCKETS_S = (
    1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1, 1.0, 3.0, 10.0,
)


class StageTracer:
    """Span recorder: ``with tracer.span("serve/solve", window=3): ...``.

    Spans nest (the record is a flat list ordered by end time, each
    naming its enclosing span's ``id`` as ``parent``); attrs must be
    JSON-serializable — the serve loop passes request uids, slot
    indices, and per-request NFE lists so a trace reconciles against
    the device-side counters (DESIGN.md §15). Attrs stay out of the
    profiler annotation's name, which is the bare span name.
    """

    #: False only on the null tracer — the serve loop assembles span
    #: attrs only when this is set
    enabled = True

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = clock if clock is not None else time.monotonic
        self.spans: List[Dict[str, Any]] = []
        self._open: List[int] = []  # ids of the spans open, innermost last
        self._next_id = 0

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        rec: Dict[str, Any] = {
            "name": name, "id": self._next_id,
            "parent": self._open[-1] if self._open else None,
            "start": self.clock(), "attrs": attrs,
        }
        self._next_id += 1
        self._open.append(rec["id"])
        try:
            with jax.profiler.TraceAnnotation(name):
                yield rec
        finally:
            self._open.pop()
            rec["end"] = self.clock()
            rec["duration_s"] = rec["end"] - rec["start"]
            self.spans.append(rec)

    def stage_histograms(self) -> Dict[str, Dict[str, Any]]:
        """Per-stage latency histograms over the recorded spans:
        count / total / mean / max plus log-spaced bucket counts
        (``LATENCY_BUCKETS_S`` bounds, final bucket +Inf)."""
        out: Dict[str, Dict[str, Any]] = {}
        for s in self.spans:
            h = out.setdefault(s["name"], {
                "count": 0, "total_s": 0.0, "max_s": 0.0,
                "buckets": [0] * (len(LATENCY_BUCKETS_S) + 1),
            })
            d = float(s["duration_s"])
            h["count"] += 1
            h["total_s"] += d
            h["max_s"] = max(h["max_s"], d)
            h["buckets"][bisect.bisect_left(LATENCY_BUCKETS_S, d)] += 1
        for h in out.values():
            h["mean_s"] = h["total_s"] / h["count"]
        return out

    def to_json(self) -> Dict[str, Any]:
        """The structured trace: every span plus the per-stage latency
        histograms (bucket bounds included so the record is
        self-describing)."""
        return {
            "spans": list(self.spans),
            "stage_histograms": self.stage_histograms(),
            "bucket_bounds_s": list(LATENCY_BUCKETS_S),
        }


class NullTracer(StageTracer):
    """The no-op default: ``span`` records nothing, reads no clock and
    opens no profiler annotation — an untraced serve loop keeps its
    pre-§15 behaviour exactly."""

    enabled = False

    def __init__(self):
        super().__init__(clock=lambda: 0.0)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        yield {"name": name, "attrs": attrs}


#: shared no-op instance (stateless — safe to share across batchers)
NULL_TRACER = NullTracer()
