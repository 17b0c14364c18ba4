"""repro.obs — deterministic-safe observability (PR 10).

One subsystem for spans, counters, fixed-bucket latency histograms,
Chrome-trace export, and JSON/Prometheus metrics snapshots, shared by
the session sweep loop, the checkpoint manager, the posterior cache,
the serving layer, and the benchmarks.  See README.md in this
directory for the span/metric catalogue and the determinism contract.
"""
from . import clock  # noqa: F401  (the sanctioned wall-clock module)
from .metrics import (Histogram, METRICS_FORMAT, TRACE_FORMAT,
                      integer_buckets, latency_buckets, percentile_summary,
                      prometheus_text, write_json_atomic)
from .recorder import Recorder, obs_enabled, resolve_recorder
from .trace_counts import count_trace, trace_counts

__all__ = [
    "Histogram", "METRICS_FORMAT", "TRACE_FORMAT", "Recorder", "clock",
    "count_trace", "integer_buckets", "latency_buckets", "obs_enabled",
    "percentile_summary", "prometheus_text", "resolve_recorder",
    "trace_counts", "write_json_atomic",
]
