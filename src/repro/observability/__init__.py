"""Unified observability: metrics registry, span tracing, hardware hooks.

The one subsystem every layer reports through (paper §operations):

* :mod:`repro.observability.metrics` — counters / gauges / bounded-
  reservoir histograms behind a :class:`MetricsRegistry` with pluggable
  sinks (JSONL, in-memory) and a stable event schema the goodput monitor's
  sink adopts.
* :mod:`repro.observability.tracing` — span :class:`Tracer` emitting
  Chrome trace-event JSON (open in Perfetto), per-rank pid lanes, fleet
  merge + schema validation.
* :mod:`repro.observability.hardware` — compiled-step FLOPs → HFU,
  ``device.memory_stats()`` gauges, on-demand ``jax.profiler`` windows.
* :mod:`repro.observability.runtime` — ``ObservabilityConfig`` + the
  per-process bundle subsystems instantiate.

Instrumented call sites: ``SpmdTrainer`` (step/data-wait/ckpt-stall spans,
summary routing, HFU gauges), ``serving.scheduler``/``gateway`` (request
lifecycle spans, latency reservoirs, queue/pool gauges), and
``launch.distributed`` workers + ``FleetSupervisor`` (per-rank traces
merged into one fleet timeline, step-boundary straggler skew).
"""

from repro.observability.hardware import (
    PEAK_FLOPS_BY_DEVICE_KIND,
    ProfilerWindow,
    compiled_cost,
    device_memory_stats,
    estimate_mfu,
    device_peak_flops,
)
from repro.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    MemorySink,
    MetricsRegistry,
)
from repro.observability.runtime import (
    Observability,
    ObservabilityConfig,
    build_observability,
)
from repro.observability.tracing import (
    Tracer,
    load_trace,
    merge_traces,
    validate_chrome_trace,
)

__all__ = [
    "PEAK_FLOPS_BY_DEVICE_KIND",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MemorySink",
    "MetricsRegistry",
    "Observability",
    "ObservabilityConfig",
    "ProfilerWindow",
    "Tracer",
    "build_observability",
    "compiled_cost",
    "device_memory_stats",
    "estimate_mfu",
    "device_peak_flops",
    "load_trace",
    "merge_traces",
    "validate_chrome_trace",
]
