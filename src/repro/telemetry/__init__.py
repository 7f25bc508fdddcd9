"""Telemetry: communication census, staleness/participation metrics,
per-client DP accounting, JSONL traces, the in-loop op census, and
span-based profiling with Perfetto timeline export, device scopes — one
``MetricsReport`` schema shared by all three engines."""
from repro.telemetry.costs import (
    N_OPS, OP_NAMES, check_ops, cost_decomposition, ops_dict, ops_vector,
    zero_ops,
)
from repro.telemetry.report import (
    HEADER_BYTES, STALE_BINS, MetricsReport, broadcast_msg_bytes,
    build_report, model_flat_dim, participation_sizes, staleness_bin,
    update_msg_bytes,
)
from repro.telemetry.scopes import DEVICE_SCOPES, op_scopes
from repro.telemetry.spans import (
    SpanRecorder, trace_to_perfetto, validate_trace_events, write_perfetto,
)
from repro.telemetry.trace import JsonlTraceWriter, open_trace

__all__ = [
    "HEADER_BYTES", "STALE_BINS", "MetricsReport", "broadcast_msg_bytes",
    "build_report", "model_flat_dim", "participation_sizes",
    "staleness_bin", "update_msg_bytes",
    "JsonlTraceWriter", "open_trace",
    "SpanRecorder", "trace_to_perfetto",
    "validate_trace_events", "write_perfetto",
    "DEVICE_SCOPES", "op_scopes",
    "N_OPS", "OP_NAMES", "check_ops", "cost_decomposition", "ops_dict",
    "ops_vector", "zero_ops",
]
