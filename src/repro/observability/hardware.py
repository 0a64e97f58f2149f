"""Hardware-utilization hooks: MFU from XLA cost analysis, HBM gauges,
and an on-demand ``jax.profiler`` window.

MFU (model FLOPs utilization) is THE cross-hardware efficiency number
(Modalities/PaLM convention): achieved model FLOP/s over the chip's peak.
The numerator comes from the *compiled* train step's own
``cost_analysis()`` — what XLA will actually execute, including remat
recompute — so it needs no analytical per-arch FLOP formula. The
denominator is a peak table keyed by ``device_kind`` (overridable per run,
``peak_flops_per_device``, because "the" peak depends on dtype). A device
that is not in the table is an error, and the CPU has no peak: MFU there is
``None``, never a number.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax

__all__ = [
    "PEAK_FLOPS_BY_DEVICE_KIND",
    "compiled_cost",
    "device_memory_stats",
    "estimate_mfu",
    "device_peak_flops",
    "ProfilerWindow",
]

# Dense bf16 peak FLOP/s of one chip, keyed by ``device.device_kind``.
# Source: Google Cloud TPU documentation, "TPU v5e" (197 TFLOP/s bf16,
# 394 TOP/s int8, 16 GB HBM at 819 GB/s per chip).
PEAK_FLOPS_BY_DEVICE_KIND: Dict[str, float] = {
    "TPU v5 lite": 197e12,
}


def device_peak_flops(device=None) -> Optional[float]:
    """The table's peak for ``device`` (default: the first device); None on
    the CPU, which has no device peak. Raises for an accelerator that is
    not in the table."""
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return PEAK_FLOPS_BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s for device kind {device.device_kind!r}; add it "
            f"to PEAK_FLOPS_BY_DEVICE_KIND with its source (known: "
            f"{sorted(PEAK_FLOPS_BY_DEVICE_KIND)})") from None


def compiled_cost(compiled) -> Dict[str, Optional[float]]:
    """FLOPs + bytes-accessed of a compiled executable via XLA's own cost
    analysis (``None`` fields when a count is zero)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)) or None,
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)) or None}


def estimate_mfu(flops_per_step: Optional[float], step_time_s: float, *,
                 num_devices: int = 1, device=None,
                 peak_flops_per_device: float = 0.0) -> Optional[float]:
    """Achieved model FLOP/s over aggregate peak. None when unmeasurable:
    no FLOPs, no step time, or a CPU ``device`` (default: the first
    device).

    ``flops_per_step`` is the GLOBAL compiled-step FLOPs (XLA reports the
    whole SPMD program); the denominator scales by ``num_devices``.
    """
    if not flops_per_step or step_time_s <= 0:
        return None
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    peak = peak_flops_per_device or device_peak_flops(device)
    return flops_per_step / (step_time_s * peak * max(num_devices, 1))


def device_memory_stats(device=None) -> Dict[str, float]:
    """Per-device memory stats (peak HBM in ``peak_bytes_in_use`` on TPU).
    Empty dict on backends without memory stats (CPU)."""
    device = device or jax.local_devices()[0]
    stats = device.memory_stats()
    if not stats:
        return {}
    return {k: float(v) for k, v in stats.items()
            if isinstance(v, (int, float))}


class ProfilerWindow:
    """On-demand ``jax.profiler`` capture of steps ``[start, stop]``.

    The trainer calls :meth:`on_step_start` / :meth:`on_step_end` at each
    step boundary; the window starts the trace before ``start`` executes
    and stops it after ``stop`` completes, writing a TensorBoard-loadable
    profile under ``logdir``. Inactive (both bounds < 0) it is two integer
    compares per step. Profiler failures (unsupported backend, busy
    session) degrade to a warning — profiling must never kill a run.
    """

    def __init__(self, logdir: str = "", *, start_step: int = -1,
                 stop_step: int = -1):
        if start_step >= 0 and stop_step < start_step:
            raise ValueError(
                f"profiler window stop_step {stop_step} precedes start_step "
                f"{start_step}")
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = stop_step
        self.active = False
        self.captured = False
        self.error: Optional[str] = None

    @property
    def enabled(self) -> bool:
        return bool(self.logdir) and self.start_step >= 0

    def on_step_start(self, step: int):
        if not self.enabled or self.active or self.captured:
            return
        if step >= self.start_step:
            try:
                jax.profiler.start_trace(self.logdir)
                self.active = True
            except Exception as e:  # noqa: BLE001
                self.error = repr(e)
                self.captured = True  # don't retry every step
                print(f"[observability] profiler start failed: {e}")

    def on_step_end(self, step: int):
        if self.active and step >= self.stop_step:
            self._stop()

    def _stop(self):
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            self.error = repr(e)
            print(f"[observability] profiler stop failed: {e}")
        self.active = False
        self.captured = True

    def close(self):
        """Stop a still-open window (run ended early / preemption)."""
        if self.active:
            self._stop()


@contextlib.contextmanager
def profiler_window(logdir: str):
    """Imperative capture of an arbitrary block (notebooks, benches)."""
    w = ProfilerWindow(logdir, start_step=0, stop_step=0)
    w.on_step_start(0)
    try:
        yield w
    finally:
        w.close()
