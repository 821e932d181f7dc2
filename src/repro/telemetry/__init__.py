"""``repro.telemetry`` — the observability front door.

Re-exports the zero-dependency core layer (``repro.core.telemetry``:
spans, the metrics registry, JSONL/Prometheus exporters) plus the
bottleneck-attribution report (:mod:`repro.telemetry.report`) that turns
``Metrics`` breakdowns into the paper's use-case-2 ranked tables.

    from repro import telemetry
    with telemetry.span("my.stage"):
        ...
    print(telemetry.prometheus_text())
    rep = telemetry.bottleneck_report(ses.evaluate(spec, net))

Enable with ``REPRO_TELEMETRY_DIR=<dir>`` (JSONL trace export) or
``telemetry.enable()`` (in-process only).  Any running ``jax.profiler``
trace receives every span's name, enabled or not.  Catalog and schema:
``docs/observability.md``.
"""
from __future__ import annotations

from ..core.telemetry import (DEFAULT_BUCKETS,  # noqa: F401
                              TELEMETRY_DIR_ENV, Histogram, count,
                              current_span, disable, enable, enabled,
                              event, gauge, observe, prometheus_text,
                              read_trace, reset, snapshot, span,
                              trace_path, validate_trace_line)
from . import report  # noqa: F401
from .report import bottleneck_report, format_report  # noqa: F401

__all__ = [
    "TELEMETRY_DIR_ENV", "DEFAULT_BUCKETS", "Histogram",
    "enable", "disable", "enabled", "reset",
    "span", "event", "count", "gauge", "observe", "current_span",
    "snapshot", "prometheus_text", "trace_path",
    "validate_trace_line", "read_trace",
    "report", "bottleneck_report", "format_report",
]
