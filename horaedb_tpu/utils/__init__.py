"""Cross-cutting utilities: observability registry + tracing spans."""

from horaedb_tpu.utils.metrics import (WIDE_BUCKETS, Counter, Gauge,
                                       Histogram, MetricsRegistry, registry)
from horaedb_tpu.utils.tracing import (active_trace, current_trace_id,
                                       new_trace_id, op_trace, phase,
                                       recorder, span, span_note,
                                       trace_add, trace_scope)

__all__ = ["WIDE_BUCKETS", "Counter", "Gauge", "Histogram",
           "MetricsRegistry", "active_trace", "current_trace_id",
           "new_trace_id", "op_trace", "phase", "recorder", "registry",
           "span", "span_note", "trace_add", "trace_scope"]
