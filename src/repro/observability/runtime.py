"""Observability bundle: one config knob that wires registry + tracer +
profiler into a subsystem (trainer, serving gateway, fleet worker).

``ObservabilityConfig`` is a plain sub-config (like ``DistributedConfig``)
so any layer can carry it; :func:`build_observability` instantiates the
runtime objects. Everything degrades to no-ops: no config → subsystems run
exactly as before (and the compile-count tests prove instrumentation adds
zero retraces when it IS on).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from repro.core.config import ConfigBase, config_class
from repro.observability.hardware import ProfilerWindow
from repro.observability.metrics import JsonlSink, MetricsRegistry
from repro.observability.tracing import Tracer

__all__ = ["ObservabilityConfig", "Observability", "build_observability"]


@config_class
class ObservabilityConfig(ConfigBase):
    """Where telemetry goes and what hardware hooks are armed.

    ``metrics_path``   — JSONL sink for the metrics/event stream ("" = in-
                         memory only; snapshots still come back in results).
    ``trace_path``     — Chrome trace-event JSON written at the end of each
                         run ("" = tracing off).
    ``profile_dir`` + ``profile_start_step``/``profile_stop_step`` — the
                         on-demand ``jax.profiler`` window (capture steps
                         N..M; -1 = off).
    ``rank``           — process index: the pid lane in merged fleet traces.
    ``mfu``            — compute compiled-step FLOPs once and gauge per-step
                         utilization ``hardware/hfu`` (costs one extra
                         lower+compile, off the step path).
    ``peak_flops_per_device`` — utilization denominator override (0 = the peak
                         table keyed by ``device_kind``).
    ``reservoir_size`` — histogram reservoir bound.
    """

    metrics_path: str = ""
    trace_path: str = ""
    profile_dir: str = ""
    profile_start_step: int = -1
    profile_stop_step: int = -1
    rank: int = 0
    mfu: bool = True
    peak_flops_per_device: float = 0.0
    reservoir_size: int = 512


class Observability:
    """Live telemetry objects for one process: ``registry``, ``tracer``
    (None when no trace_path), ``profiler``."""

    def __init__(self, cfg: ObservabilityConfig):
        self.config = cfg
        sinks = [JsonlSink(cfg.metrics_path)] if cfg.metrics_path else []
        self.registry = MetricsRegistry(sinks=sinks,
                                        reservoir_size=cfg.reservoir_size)
        self.tracer: Optional[Tracer] = None
        if cfg.trace_path:
            self.tracer = Tracer(pid=cfg.rank,
                                 process_name=f"rank {cfg.rank}")
        self.profiler = ProfilerWindow(cfg.profile_dir,
                                       start_step=cfg.profile_start_step,
                                       stop_step=cfg.profile_stop_step)

    def save_trace(self) -> Optional[str]:
        if self.tracer is not None and self.config.trace_path:
            return self.tracer.save(self.config.trace_path)
        return None

    def snapshot(self) -> Dict[str, Any]:
        return self.registry.snapshot()

    def close(self):
        self.profiler.close()
        self.save_trace()
        self.registry.close()


def build_observability(cfg: Optional[ObservabilityConfig]
                        ) -> Optional[Observability]:
    return Observability(cfg) if cfg is not None else None
