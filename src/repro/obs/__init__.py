"""repro.obs — unified telemetry: structured metrics, spans, profiler hooks.

See docs/observability.md for the record schema, span semantics and the
profiler workflow. Entry points:

  Telemetry / make_telemetry   the bus (sinks, rounds, stats, spans, close)
  NULL                         shared no-op bus for uninstrumented runs
  JsonlSink / MemorySink
  run_manifest                 the schema-versioned run header
  StatAccum                    on-device [K, S] stat ring, one transfer per K
  progress_line                the shared launcher progress formatter
"""
from repro.obs.telemetry import (NULL, SCHEMA, JsonlSink, MemorySink,
                                 NullTelemetry, Telemetry, make_telemetry,
                                 run_manifest)
from repro.obs.devstats import STAT_FIELDS, StatAccum, stat_row
from repro.obs.progress import progress_line

__all__ = [
    "NULL", "SCHEMA", "JsonlSink", "MemorySink", "NullTelemetry",
    "Telemetry", "make_telemetry", "run_manifest",
    "STAT_FIELDS", "StatAccum", "stat_row", "progress_line",
]
