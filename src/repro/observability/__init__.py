"""Observability layer (DESIGN.md §15): on-device solver telemetry,
serve-loop span tracing, a metrics registry with JSON/Prometheus export,
and quality-proxy gauges.

Everything here is off by default and structurally invisible when off:
the telemetry ring rides ``SolverCarry.telemetry`` as a None-by-default
pytree field (telemetry-off carries keep their exact pre-§15 treedef and
trace bitwise-identical programs), the tracer defaults to a no-op
singleton, and the metrics registry only generalizes counters the serve
loop already kept.
"""

from repro.observability.metrics import MetricsRegistry
from repro.observability.quality import (
    dynamics_consistency,
    env_step_mean,
    feature_moments,
    frechet_from_moments,
    proxy_fid,
    random_feature_extractor,
)
from repro.observability.telemetry import (
    StepTelemetry,
    init_telemetry,
    record_step,
    telemetry_history,
)
from repro.observability.tracing import (
    NULL_TRACER,
    NullTracer,
    StageTracer,
)

__all__ = [
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "StageTracer",
    "StepTelemetry",
    "dynamics_consistency",
    "env_step_mean",
    "feature_moments",
    "frechet_from_moments",
    "init_telemetry",
    "proxy_fid",
    "random_feature_extractor",
    "record_step",
    "telemetry_history",
]
