"""Unified observability layer: TelemetryHub + its sources.

See ``docs/observability.md`` for the config surface
(``wall_clock_breakdown``, ``memory_breakdown``, ``comms_logger``,
``profiler``, ``telemetry.trace``, ``telemetry.compile`` (recompilation
sentinel + per-program MFU attribution), ``telemetry.anomaly`` (step-time
spike/drift/straggler detection), monitor backends incl. the size-rotated
JSONL sink, and the pull-based Prometheus metrics endpoint), plus the
fleet observability plane (``serving.obs``: cross-replica request tracing,
per-tenant SLO accounting with burn-rate alerting, and the bounded
in-memory time-series store behind ``GET /series``).
"""

from .anomaly import AnomalyConfig, AnomalyDetector  # noqa: F401
from .fleet import (FleetMetricsAggregator, FleetObsConfig,  # noqa: F401
                    FleetObservability, TenantSLOAccountant, TraceContext,
                    tenant_slug)
from .compile import (CompileMonitor, CompileMonitorConfig,  # noqa: F401
                      RecompileBudgetExceeded, peak_flops_total)
from .hub import TelemetryHub  # noqa: F401
from .memory import MemoryTelemetry  # noqa: F401
from .metrics_server import MetricsServer  # noqa: F401
from .profiler import ProfilerSession  # noqa: F401
from .schema import (ANOMALY_SERIES, COMPILE_METRICS,  # noqa: F401
                     SERVING_SERIES, validate_events,
                     validate_jsonl_records)
from .trace import TraceConfig, Tracer, dump_all, percentiles  # noqa: F401
from .tsdb import TimeSeriesStore, TsdbConfig  # noqa: F401
