"""Serve-loop span tracing (DESIGN.md §15): monotonic-clock spans over
the batcher's admission/solve/delivery stages and planner rounds, plus
``jax.profiler`` annotation hooks around the jitted device programs.

The tracer is deliberately minimal — a list of ``{name, start, end,
duration_s, attrs}`` dicts on an injectable monotonic clock — because
the interesting structure (request-id propagation through compaction,
per-stage latency distributions) lives in the *attrs* the serve loop
attaches, not in the recording machinery. ``NULL_TRACER`` is the
default no-op: its ``span`` yields without recording, so an untraced
batcher does no clock reads and allocates nothing per stage.
"""

from __future__ import annotations

import bisect
import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

#: log-spaced latency bucket upper bounds (seconds) for the per-stage
#: histograms; the final implicit bucket is +Inf
LATENCY_BUCKETS_S = (
    1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1, 1.0, 3.0, 10.0,
)


class StageTracer:
    """Span recorder: ``with tracer.span("serve/solve", window=3): ...``.

    Spans nest freely (the record is a flat list ordered by end time);
    attrs must be JSON-serializable — the serve loop passes request
    uids, slot indices, and per-request NFE lists so a trace reconciles
    against the device-side counters (DESIGN.md §15).
    """

    #: False only on the null tracer — the serve loop keys optional
    #: extras (profiler annotations, attr assembly) on this flag
    enabled = True

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = clock if clock is not None else time.monotonic
        self.spans: List[Dict[str, Any]] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        rec: Dict[str, Any] = {"name": name, "start": self.clock(),
                               "attrs": attrs}
        try:
            yield rec
        finally:
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
    """The no-op default: ``span`` records nothing and reads no clock —
    an untraced serve loop pays one ``is not None``-grade check per
    stage and keeps its pre-§15 behaviour exactly."""

    enabled = False

    def __init__(self):
        super().__init__(clock=lambda: 0.0)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        yield {"name": name, "attrs": attrs}


#: shared no-op instance (stateless — safe to share across batchers)
NULL_TRACER = NullTracer()


def profiler_annotation(name: str, step: Optional[int] = None):
    """A ``jax.profiler`` trace-annotation context for the given stage:
    ``StepTraceAnnotation`` when a step number is given (so profiler
    UIs group the donated driver's windows), ``TraceAnnotation``
    otherwise. Both are cheap no-ops without an active profiler."""
    import jax

    if step is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=int(step))
    return jax.profiler.TraceAnnotation(name)
