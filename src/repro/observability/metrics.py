"""Metrics registry: counters, gauges, bounded-reservoir histograms, sinks.

The ONE place every layer reports numbers through (paper §operations):
the trainer's step summaries, the serving gateway's latency percentiles,
and the fleet workers' goodput streams all flow into a
:class:`MetricsRegistry` so a run has a single, uniformly-schemed telemetry
stream instead of per-subsystem ad-hoc lists.

Design constraints (enforced by ``tests/test_observability.py``):

* **Hot-path cost is a dict update.** ``Counter.inc`` / ``Gauge.set`` /
  ``Histogram.record`` touch only in-process state — no I/O, no locks, no
  string formatting. Sinks see data when :meth:`MetricsRegistry.flush` is
  called (the trainer flushes at its logging cadence) or when an *event*
  is recorded explicitly.
* **Bounded memory.** Histograms keep a fixed-size uniform reservoir
  (Vitter's algorithm R, deterministic RNG) plus exact count/sum/min/max —
  p50/p99 snapshots over millions of samples at O(reservoir) bytes. This
  is what fixed the serving gateway's unbounded TTFT/TPOT lists.
* **Stable event schema.** Every record emitted to a sink is one flat JSON
  object: ``{"schema": 1, "kind": ..., "name": ..., "t": ..., ...}`` with
  ``kind`` in {"counter", "gauge", "histogram", "event", "meta"}. The
  goodput monitor's structured events adopt the same schema through
  :meth:`MetricsRegistry.goodput_sink`.

Sinks: :class:`JsonlSink` (one JSON object per line, append-only, the
format the fleet supervisor and offline analysis read) and
:class:`MemorySink` (tests).
"""

from __future__ import annotations

import json
import random
import time
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "SCHEMA_VERSION",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MemorySink",
    "MetricsRegistry",
]

SCHEMA_VERSION = 1

# Fields every sink record carries (the stable part of the schema; kinds
# add their own value fields on top).
RECORD_BASE_FIELDS = ("schema", "kind", "name", "t")


def _dumps_line(r: Dict[str, Any]) -> str:
    """One JSONL line. Fast path: flat records of simple-keyed scalars
    (every record the registry itself builds) serialize with repr — ~3x
    faster than json.dumps on small dicts, which is the whole cost of a
    per-log-step flush. Anything else falls back to json.dumps."""
    parts = []
    for k, v in r.items():
        if '"' in k or "\\" in k:
            return json.dumps(r) + "\n"
        tv = type(v)  # EXACT types only: a np.float64 passes isinstance
        # float checks but reprs as "np.float64(...)" — not JSON.
        if tv is float or tv is int:
            if v != v or v in (float("inf"), float("-inf")):
                return json.dumps(r) + "\n"  # non-finite: let json handle
            parts.append(f'"{k}":{v!r}')
        elif tv is bool:
            parts.append(f'"{k}":{"true" if v else "false"}')
        elif v is None:
            parts.append(f'"{k}":null')
        elif tv is str and v.isprintable() and '"' not in v \
                and "\\" not in v:
            parts.append(f'"{k}":"{v}"')
        else:
            return json.dumps(r) + "\n"
    return "{" + ",".join(parts) + "}\n"


def _jsonable(v: Any) -> Any:
    """Scalars pass through; arrays/np scalars collapse to float; the rest
    is stringified — a sink line must always be loadable JSON."""
    if v is None or type(v) in (bool, int, float, str):
        return v
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)


class JsonlSink:
    """Append-only JSONL file sink (one record per line).

    Records are serialized on arrival but the file write is buffered
    (``buffer_records`` lines) so a per-step flush costs string building,
    not syscalls; a crashed process loses at most the buffered tail. The
    trainer's every-exit ``registry.flush()`` + ``close()`` drain it."""

    def __init__(self, path: str, *, buffer_records: int = 64):
        self.path = path
        self.buffer_records = buffer_records
        self._f = open(path, "a")
        self._buf: List[str] = []

    def __call__(self, records: List[Dict[str, Any]]):
        self._buf.extend(_dumps_line(r) for r in records)
        if len(self._buf) >= self.buffer_records:
            self.flush()

    def flush(self):
        if self._buf:
            self._f.write("".join(self._buf))
            self._buf.clear()
            self._f.flush()

    def close(self):
        if self._f is not None:
            self.flush()
            self._f.close()
            self._f = None


class MemorySink:
    """Keeps every record in a list — the test sink."""

    def __init__(self):
        self.records: List[Dict[str, Any]] = []

    def __call__(self, records: List[Dict[str, Any]]):
        self.records.extend(records)

    def close(self):
        pass


class Counter:
    """Monotonic count (requests served, tokens emitted, retries)."""

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def inc(self, n: float = 1.0):
        self.value += n

    def snapshot(self) -> Dict[str, Any]:
        return {"value": self.value}


class Gauge:
    """Last-write-wins instantaneous value (loss, queue depth, HBM bytes)."""

    def __init__(self, name: str):
        self.name = name
        self.value: Optional[float] = None
        self.updates = 0

    def set(self, v: float):
        self.value = v
        self.updates += 1

    def snapshot(self) -> Dict[str, Any]:
        return {"value": self.value, "updates": self.updates}


class Histogram:
    """Bounded-reservoir distribution (latencies, span durations).

    Uniform reservoir sampling (algorithm R): after N records, each sample
    survives with probability ``size/N`` — percentiles stay statistically
    representative of the WHOLE stream at fixed memory, unlike a
    keep-everything list (which the serving gateway used to grow for the
    process lifetime) or a keep-last window (which forgets warm-up tails).
    min/max/sum/count are tracked exactly.
    """

    def __init__(self, name: str, *, reservoir_size: int = 512, seed: int = 0):
        if reservoir_size < 1:
            raise ValueError(f"reservoir_size must be >= 1, got {reservoir_size}")
        self.name = name
        self.reservoir_size = reservoir_size
        self._rng = random.Random(seed)
        self.values: List[float] = []
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def record(self, v: float):
        v = float(v)
        self.count += 1
        self.total += v
        if self.min is None or v < self.min:
            self.min = v
        if self.max is None or v > self.max:
            self.max = v
        if len(self.values) < self.reservoir_size:
            self.values.append(v)
        else:
            j = self._rng.randrange(self.count)
            if j < self.reservoir_size:
                self.values[j] = v

    def percentile(self, p: float, *, _sorted: Optional[List[float]] = None,
                   ) -> float:
        xs = sorted(self.values) if _sorted is None else _sorted
        if not xs:
            return 0.0
        # Nearest-rank on the reservoir (matches np.percentile 'lower'
        # closely enough for telemetry; avoids importing numpy here).
        idx = min(int(round((p / 100.0) * (len(xs) - 1))), len(xs) - 1)
        return xs[idx]

    def snapshot(self) -> Dict[str, Any]:
        xs = sorted(self.values)  # one sort shared by all percentiles
        return {
            "count": self.count,
            "sum": self.total,
            "min": self.min if self.min is not None else 0.0,
            "max": self.max if self.max is not None else 0.0,
            "mean": (self.total / self.count) if self.count else 0.0,
            "p50": self.percentile(50, _sorted=xs),
            "p90": self.percentile(90, _sorted=xs),
            "p99": self.percentile(99, _sorted=xs),
            "reservoir_len": len(self.values),
        }


class MetricsRegistry:
    """Named instruments + pluggable sinks behind one stable schema.

    Instruments are get-or-create by name (``registry.counter("x")`` twice
    returns the same object), so call sites never coordinate registration.
    """

    def __init__(self, *, sinks: Optional[List[Callable]] = None,
                 reservoir_size: int = 512,
                 time_fn: Callable[[], float] = time.time):
        self._sinks: List[Callable] = list(sinks or [])
        self._reservoir_size = reservoir_size
        self._time = time_fn
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        # Versions at the last flush: flush() emits a DELTA stream (only
        # instruments that changed), so a steady gauge costs nothing per
        # logging interval.
        self._flushed: Dict[Any, float] = {}

    # ----------------------------------------------------------- instruments

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = Counter(name)
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges[name] = Gauge(name)
        return g

    def histogram(self, name: str,
                  reservoir_size: Optional[int] = None) -> Histogram:
        h = self._histograms.get(name)
        if h is None:
            h = self._histograms[name] = Histogram(
                name, reservoir_size=reservoir_size or self._reservoir_size)
        return h

    # ---------------------------------------------------------------- events

    def _record(self, kind: str, name: str, *, t: Optional[float] = None,
                **fields) -> Dict[str, Any]:
        return {"schema": SCHEMA_VERSION, "kind": kind, "name": name,
                "t": self._time() if t is None else t,
                **{k: _jsonable(v) for k, v in fields.items()}}

    def record_event(self, name: str, **fields):
        """A one-off structured event, emitted to sinks immediately (the
        streaming part of the schema — goodput buckets, faults, restarts)."""
        self._emit([self._record("event", name, **fields)])

    def goodput_sink(self) -> Callable[[dict], None]:
        """Adapter: pass as ``GoodputMonitor(sink=registry.goodput_sink())``
        and every wall-time bucket event lands in the unified stream as
        ``{"kind": "event", "name": "goodput/<bucket>", "dur_s": ...}``."""

        def sink(event: dict):
            meta = {k: v for k, v in event.items() if k != "bucket"}
            self.record_event(f"goodput/{event['bucket']}", **meta)

        return sink

    # ------------------------------------------------------------- reporting

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time view of every instrument (no sink I/O)."""
        return {
            "counters": {n: c.snapshot() for n, c in self._counters.items()},
            "gauges": {n: g.snapshot() for n, g in self._gauges.items()},
            "histograms": {n: h.snapshot()
                           for n, h in self._histograms.items()},
        }

    def flush(self):
        """Emit one record per instrument *changed since the last flush* to
        the sinks (the batched, non-hot-path half of the schema — a delta
        stream, so unchanging instruments cost nothing per interval)."""
        records = []
        now = self._time()  # one clock read per batch, not per record
        # Counter/gauge records are built inline (not via _record) — this
        # loop runs every trainer logging step, and the extra snapshot +
        # kwargs-merge dicts were a measurable slice of the step budget.
        for n, c in self._counters.items():
            if self._flushed.get(("c", n)) != c.value:
                self._flushed[("c", n)] = c.value
                records.append(
                    {"schema": SCHEMA_VERSION, "kind": "counter", "name": n,
                     "t": now, "value": _jsonable(c.value)})
        for n, g in self._gauges.items():
            if self._flushed.get(("g", n)) != g.updates:
                self._flushed[("g", n)] = g.updates
                records.append(
                    {"schema": SCHEMA_VERSION, "kind": "gauge", "name": n,
                     "t": now, "value": _jsonable(g.value),
                     "updates": g.updates})
        for n, h in self._histograms.items():
            if self._flushed.get(("h", n)) != h.count:
                self._flushed[("h", n)] = h.count
                records.append(self._record("histogram", n, t=now,
                                            **h.snapshot()))
        if records:
            self._emit(records)

    def drain(self):
        """:meth:`flush` plus a durability flush of every buffering sink —
        the run-exit path (a sink's write buffer does not survive process
        exit on its own)."""
        self.flush()
        for sink in self._sinks:
            f = getattr(sink, "flush", None)
            if f is not None:
                f()

    def _emit(self, records: List[Dict[str, Any]]):
        for sink in self._sinks:
            sink(records)

    def add_sink(self, sink: Callable):
        self._sinks.append(sink)

    def close(self):
        self.flush()
        for sink in self._sinks:
            close = getattr(sink, "close", None)
            if close is not None:
                close()
