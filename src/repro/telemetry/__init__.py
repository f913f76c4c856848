"""End-to-end serving telemetry (ISSUE 7): request-lifecycle spans, memctl
lane timelines, and Perfetto/Prometheus exporters.

``EngineConfig.telemetry = TelemetryConfig()`` turns it on; the default is
the no-op :data:`NULL_COLLECTOR`, so a disabled serving path pays one
branch per instrumentation site and stays bit-identical.  See
:mod:`repro.telemetry.collector` for the event model.

:mod:`repro.telemetry.steps` is always on: the phases and counters of each
scheduler step, as profiler annotations and in a bounded step log.
"""

from repro.telemetry.collector import (  # noqa: F401
    NULL_COLLECTOR,
    NullCollector,
    RequestSpan,
    Stamp,
    TelemetryCollector,
    TelemetryConfig,
    make_collector,
    quantiles,
)
from repro.telemetry.perfetto import (  # noqa: F401
    build_trace_events,
    validate_trace,
    write_perfetto_trace,
)
from repro.telemetry.prometheus import prometheus_snapshot  # noqa: F401
from repro.telemetry import steps  # noqa: F401
from repro.telemetry.steps import PHASES, StepLog  # noqa: F401
