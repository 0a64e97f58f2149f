"""What every cell shares: finding its files by name, the chip check, the
profiler window, the metric readers and the result line."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def setup_environment(root: str):
    """Before JAX is imported: the compile cache inside the checkout (a
    fixed path, so a second run finds every program), the program's sources
    on the path, and no log directory outside the checkout."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    program_on_path(root)


def program_on_path(root: str):
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Imports a file by path (metric names hold dots, so not by name)."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: Dict[str, Any], cell: str) -> bool:
    """Whether a metric is reported in ``cell``: an end-to-end metric with
    no ``workloads`` list is reported everywhere; every per-layer metric
    lists its cells."""
    return cell in entry.get("workloads", [cell])


def prepare(root: str, args, *, t_start: float,
            overrides: Optional[Dict[str, Any]] = None,
            bench: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The run's context; ``bench`` stands for ``BENCHMARK.json`` where a
    cell is not listed there yet (``chipbench/pending/``)."""
    bench = bench or load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"chipbench: no workload {args.workload!r} in BENCHMARK.json "
                 f"(known: {sorted(cells)})")
    cell = cells[args.workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    config["name"] = entry["name"]
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    limits_path = os.path.join(HERE, "limits", f"{cell['name']}.json")
    limits = load_json(limits_path) if os.path.exists(limits_path) else {}
    overrides = overrides or {}
    config.update(overrides.get("config", {}))
    mix.update(overrides.get("mix", {}))
    e2e = [m for m in bench["end_to_end"] if applies(m, cell["name"])]
    layer = [m for m in bench["per_layer"] if cell["name"] in m["workloads"]]
    return {
        "root": root, "t_start": t_start, "seed": int(args.seed),
        "seconds": float(args.seconds), "tracing": bool(args.trace),
        "cell": cell, "config": config, "mix": mix,
        "limits": limits.get("checks", {}),
        "metrics": layer if args.trace else e2e,
        "fault": overrides.get("fault"),
        "records": {}, "checks": [],
    }


def require_chips(n: int):
    """The cell's chips, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU (JAX platform {devices[0].platform!r}); "
                 "refusing to measure on another backend")
    if len(devices) < n:
        sys.exit(f"chipbench: the cell needs {n} chips, JAX sees "
                 f"{len(devices)}")
    return devices[:n]


# ------------------------------------------------------------ the window


def annotate(ctx, name: str):
    """A host span in the profiler's trace (the traced run only)."""
    if not ctx["tracing"]:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def trace_dir(ctx) -> str:
    return os.path.join(ctx["root"], "build", "chipbench_trace",
                        ctx["cell"]["name"])


def start_trace(ctx):
    if not ctx["tracing"]:
        return
    import jax

    path = trace_dir(ctx)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)


def stop_trace(ctx):
    if not ctx["tracing"]:
        return
    import jax

    jax.profiler.stop_trace()


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts backend compilations between ``start`` and ``stop`` (the
    window should see none). One listener per process."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax.monitoring as mon

            cls._instance = super().__new__(cls)
            cls._instance.active = False
            cls._instance.count = 0
            mon.register_event_duration_secs_listener(
                cls._instance._on_event)
        return cls._instance

    def _on_event(self, event, duration, **_):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def start(self):
        self.count, self.active = 0, True

    def stop(self):
        self.active = False
        return self.count


# ------------------------------------------------------------ the result


def check(ctx, name: str, value: float):
    """Records one compared number beside its limit (None: no limit set,
    which fails)."""
    ctx["checks"].append((name, float(value), ctx["limits"].get(name)))


def run_cell(ctx, devices) -> Dict[str, Any]:
    from chipbench import trace_reduce

    kind = devices[0].device_kind
    ctx["devices"] = devices
    driver = load_module(os.path.join(HERE, "drivers",
                                      f"{ctx['config']['driver']}.py"))
    driver.run(ctx)
    if ctx["tracing"]:
        ctx["trace"] = trace_reduce.reduce_dir(
            trace_dir(ctx), window="bench.window",
            annotations=ctx.get("annotations", ()))
    metrics = {}
    for m in ctx["metrics"]:
        reader = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"))
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = ctx["checks"]
    correct = bool(checks) and ctx["failed"] == 0 and all(
        lim is not None and math.isfinite(v) and v <= lim
        for _, v, lim in checks)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": int(ctx["memory_peak_bytes"])}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": int(ctx["attempted"]),
        "failed": int(ctx["failed"]), "metrics": metrics, "device": device}
    if ctx["tracing"]:
        tr = ctx["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"][:10],
                               "idle_gaps": tr["idle_by_host"][:10]}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result


def emit(result: Dict[str, Any]):
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
