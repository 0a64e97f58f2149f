"""Span tracer emitting Chrome trace-event JSON (openable in Perfetto).

One :class:`Tracer` per process. Spans are *complete* events (``ph: "X"``)
with microsecond timestamps; rank identity is the Chrome ``pid`` so a
merged fleet trace renders one lane per rank (``merge_traces``). The
timeline a human opens in https://ui.perfetto.dev shows, per rank: the
step/data-wait/checkpoint-stall phases of every training step, and per
request: queued → prefill → decode lanes on the serving side.

All tracing is host-side and outside jit — a span is two ``time`` calls
and a dict append, so instrumenting the hot loop adds zero retraces and
microsecond-scale per-span cost.
A ``span`` is also a ``jax.profiler.TraceAnnotation`` of the same name, so
while a profiler capture is open (``ProfilerWindow``) the phase appears on
the device trace's clock beside the device ops it dispatched.

Wall-clock (``time.time``) is the default timebase so traces from
different processes on one host merge onto a common axis. (Cross-host
merging would need a clock-sync offset per host; the local fleet substrate
shares one clock.)

``validate_chrome_trace`` is the schema gate used by tests and the fleet
supervisor: required keys per event, non-negative durations, and proper
span nesting per (pid, tid) lane — two spans on one lane either nest or
are disjoint, never partially overlap.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Union

import jax

__all__ = ["Tracer", "merge_traces", "validate_chrome_trace", "load_trace"]


def _jsonable(v: Any) -> Any:
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)


class Tracer:
    """Collects Chrome trace events for one process (= one pid lane)."""

    def __init__(self, *, pid: int = 0, process_name: Optional[str] = None,
                 time_fn: Callable[[], float] = time.time):
        self.pid = pid
        self._time = time_fn
        self.events: List[Dict[str, Any]] = []
        self._meta: List[Dict[str, Any]] = []
        if process_name is not None:
            self.set_process_name(process_name)

    # -------------------------------------------------------------- metadata

    def set_process_name(self, name: str):
        self._meta.append({"name": "process_name", "ph": "M", "pid": self.pid,
                           "tid": 0, "args": {"name": name}})

    def set_thread_name(self, tid: int, name: str):
        self._meta.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                           "tid": tid, "args": {"name": name}})

    # ----------------------------------------------------------------- spans

    @contextlib.contextmanager
    def span(self, name: str, *, tid: int = 0, **args):
        """Wraps a block in a complete ("X") event and a profiler
        annotation. Nesting follows the call stack naturally: an inner
        span's [ts, ts+dur] lies inside the outer's, which is exactly what
        the Chrome viewer stacks."""
        t0 = self._time()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            t1 = self._time()
            self.add_span(name, t0, t1, tid=tid, **args)

    def add_span(self, name: str, t_start_s: float, t_end_s: float, *,
                 tid: int = 0, **args):
        """A span with explicit endpoints — for lifecycles measured by
        timestamps already on hand (per-request queued/prefill/decode)."""
        self.events.append({
            "name": name, "ph": "X", "pid": self.pid, "tid": tid,
            "ts": t_start_s * 1e6,
            "dur": max(t_end_s - t_start_s, 0.0) * 1e6,
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    def instant(self, name: str, *, tid: int = 0, **args):
        self.events.append({
            "name": name, "ph": "i", "s": "t", "pid": self.pid, "tid": tid,
            "ts": self._time() * 1e6,
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    def counter(self, name: str, value: float, *, tid: int = 0):
        """A counter-track sample (queue depth, page-pool utilization) —
        Perfetto renders these as a line chart under the process."""
        self.events.append({
            "name": name, "ph": "C", "pid": self.pid, "tid": tid,
            "ts": self._time() * 1e6, "args": {"value": _jsonable(value)}})

    # ------------------------------------------------------------------- I/O

    def to_chrome(self) -> Dict[str, Any]:
        return {"traceEvents": self._meta + self.events,
                "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


def load_trace(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def merge_traces(traces: Iterable[Union[str, Dict[str, Any], Tracer]], *,
                 out_path: Optional[str] = None) -> Dict[str, Any]:
    """Merges per-rank traces into ONE Chrome trace object.

    Each input keeps its own pid (the rank), so the merged file renders one
    process lane per rank. Inputs may be paths, already-loaded trace dicts,
    or live :class:`Tracer` objects. Duplicate process_name metadata from
    restart attempts is deduped (the lane persists across restarts — a rank
    that died and came back continues on the same lane).
    """
    events: List[Dict[str, Any]] = []
    seen_meta = set()
    for t in traces:
        if isinstance(t, Tracer):
            obj = t.to_chrome()
        elif isinstance(t, str):
            obj = load_trace(t)
        else:
            obj = t
        for e in obj.get("traceEvents", []):
            if e.get("ph") == "M":
                key = (e.get("pid"), e.get("tid"), e.get("name"),
                       json.dumps(e.get("args", {}), sort_keys=True))
                if key in seen_meta:
                    continue
                seen_meta.add(key)
            events.append(e)
    merged = {"traceEvents": events, "displayTimeUnit": "ms"}
    if out_path is not None:
        with open(out_path, "w") as f:
            json.dump(merged, f)
    return merged


def validate_chrome_trace(obj: Dict[str, Any]) -> Dict[str, Any]:
    """Validates a Chrome trace-event object; raises ValueError on the
    first violation. Returns summary stats (span/lane counts) on success.

    Checks: top-level shape, per-event required keys, non-negative ts/dur,
    and well-formed nesting per (pid, tid) lane — sorted by start time,
    every span must either contain or be disjoint from the next (a partial
    overlap means the producer emitted garbage endpoints).
    """
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("trace must be a dict with a 'traceEvents' list")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    lanes: Dict[Any, List[Dict[str, Any]]] = {}
    n_spans = 0
    for i, e in enumerate(events):
        for key in ("name", "ph", "pid", "tid"):
            if key not in e:
                raise ValueError(f"event {i} missing required key {key!r}: {e}")
        ph = e["ph"]
        if ph == "M":
            continue
        if "ts" not in e:
            raise ValueError(f"event {i} ({e['name']}) has no 'ts'")
        if ph == "X":
            if "dur" not in e or e["dur"] < 0:
                raise ValueError(
                    f"event {i} ({e['name']}) needs a non-negative 'dur'")
            n_spans += 1
            lanes.setdefault((e["pid"], e["tid"]), []).append(e)
    for (pid, tid), spans in lanes.items():
        spans = sorted(spans, key=lambda e: (e["ts"], -e["dur"]))
        stack: List[Dict[str, Any]] = []
        # Tolerance for float-microsecond rounding at shared boundaries.
        eps = 0.5
        for e in spans:
            end = e["ts"] + e["dur"]
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - eps:
                stack.pop()
            if stack and end > stack[-1]["ts"] + stack[-1]["dur"] + eps:
                raise ValueError(
                    f"lane (pid={pid}, tid={tid}): span {e['name']!r} "
                    f"[{e['ts']}, {end}] partially overlaps enclosing "
                    f"{stack[-1]['name']!r}")
            stack.append(e)
    return {
        "num_events": len(events),
        "num_spans": n_spans,
        "pids": sorted({e["pid"] for e in events}),
        "lanes": len(lanes),
    }
