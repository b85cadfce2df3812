"""repro.obs — unified observability: metrics registry, execution tracing,
predicted-vs-measured drift tracking.

Quick tour::

    from repro import obs

    obs.enable()                         # tracing on (off by default)
    with obs.span("plan_build", algorithm="ring_c"):
        ...                              # spans nest, thread-safe, and
                                         # reach a jax.profiler trace
    obs.export_trace("trace.json")       # Chrome-trace JSON for Perfetto

    obs.enable(drift=True)               # plan calls also block and
                                         # record predicted-vs-measured

    obs.registry().counter("steal3d.plans_built").inc()
    obs.registry().snapshot()            # plain-dict view of every metric

    obs.drift_report()                   # cost-model calibration per series

Importing this package never imports jax — benches may import it at module
scope before platform flags are set; ``enable()`` and the timing helpers
defer their jax import to call time.
"""
from .drift import (
    drift_records,
    drift_report,
    export_drift,
    record_drift,
    reset_drift,
)
from .registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    percentile,
    registry,
)
from .trace import (
    REQUIRED_EVENT_KEYS,
    clear_trace,
    disable,
    drift_enabled,
    enable,
    enabled,
    events,
    export_trace,
    span,
    sync_elapsed,
    timed,
    validate_trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REQUIRED_EVENT_KEYS",
    "clear_trace",
    "disable",
    "drift_enabled",
    "drift_records",
    "drift_report",
    "enable",
    "enabled",
    "events",
    "export_drift",
    "export_trace",
    "percentile",
    "record_drift",
    "registry",
    "reset_all",
    "reset_drift",
    "span",
    "sync_elapsed",
    "timed",
    "validate_trace",
]


def reset_all() -> None:
    """Clear trace buffer, drift series, and zero the default registry."""
    clear_trace()
    reset_drift()
    registry().reset()
