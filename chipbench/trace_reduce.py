"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* Device time: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane. An
  op's interval is clipped to the measured window, which is the host span
  named ``window`` (the benchmark's own ``TraceAnnotation``).
* Busy time is the union of a device's op intervals; idle time is the rest
  of the window. Both are averaged over the devices traced.
* Op time is the sum of clipped durations per op name, averaged over the
  devices; a reader matches kernel names against it.
* Every idle gap of a device is attributed to the host spans named in
  ``annotations`` that overlap it (time under none goes to ``(none)``).
* Collective time is the union of collective ops (all-reduce,
  reduce-scatter, all-gather, collective-permute, all-to-all); its exposed
  part is what no other op on that device overlaps.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all",
    re.IGNORECASE)

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def complement(intervals: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    out, t = [], lo
    for a, b in intervals:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def subtract(base: Sequence[Interval], cut: Sequence[Interval]) -> float:
    """Length of ``base`` (a union) not covered by ``cut`` (a union)."""
    total, j = 0.0, 0
    for a, b in base:
        covered = 0.0
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            covered += min(b, cut[k][1]) - max(a, cut[k][0])
            k += 1
        total += (b - a) - covered
    return total


def load_events(path: str):
    """(host spans by name, device ops by plane) from one xplane file, in
    seconds on the profiler's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: Dict[str, List[Interval]] = defaultdict(list)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    t = ev.start_ns * 1e-9
                    ops.append((ev.name, t, t + ev.duration_ns * 1e-9))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    t = ev.start_ns * 1e-9
                    host[ev.name].append((t, t + ev.duration_ns * 1e-9))
    return host, devices


def reduce_events(host, devices, *, window: str,
                  annotations: Sequence[str] = ()) -> Dict:
    if not host.get(window):
        raise ValueError(f"no host span {window!r} in the trace")
    lo = min(a for a, _ in host[window])
    hi = max(b for _, b in host[window])
    spans = {n: union((max(a, lo), min(b, hi)) for a, b in host.get(n, ()))
             for n in annotations}
    busy, coll, exposed = [], [], []
    op_time: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    n_dev = max(len(devices), 1)
    for plane in sorted(devices):
        clipped = [(n, max(a, lo), min(b, hi)) for n, a, b in devices[plane]
                   if b > lo and a < hi]
        u = union((a, b) for _, a, b in clipped)
        busy.append(length(u))
        for n, a, b in clipped:
            op_time[n] += (b - a) / n_dev
            op_count[n] += 1.0 / n_dev
        for g0, g1 in complement(u, lo, hi):
            left = g1 - g0
            for name, ivs in spans.items():
                for a, b in ivs:
                    o = min(b, g1) - max(a, g0)
                    if o > 0:
                        idle_by[name] += o / n_dev
                        left -= o
            if left > 0:
                idle_by["(none)"] += left / n_dev
        cu = union((a, b) for n, a, b in clipped if COLLECTIVE.search(n))
        other = union((a, b) for n, a, b in clipped
                      if not COLLECTIVE.search(n))
        coll.append(length(cu))
        exposed.append(subtract(cu, other))
    window_s = hi - lo
    return {
        "window_s": window_s,
        "devices": len(devices),
        "busy_s": sum(busy) / n_dev,
        "idle_share": 1.0 - sum(busy) / n_dev / window_s if devices else None,
        "op_time": dict(op_time),
        "op_count": dict(op_count),
        "top_ops": sorted(([k, v] for k, v in op_time.items()),
                          key=lambda kv: -kv[1]),
        "idle_by_host": sorted(([k, v] for k, v in idle_by.items()),
                               key=lambda kv: -kv[1]),
        "collective_s": sum(coll) / n_dev,
        "collective_exposed_s": sum(exposed) / n_dev,
    }


def find_xplane(directory: str) -> str:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def reduce_dir(directory: str, *, window: str,
               annotations: Sequence[str] = ()) -> Dict:
    host, devices = load_events(find_xplane(directory))
    return reduce_events(host, devices, window=window,
                         annotations=annotations)


def ops_matching(trace: Dict, pattern: str) -> float:
    """Seconds of device time in ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in trace["op_time"].items() if rx.search(k))


def calls_matching(trace: Dict, pattern: str) -> float:
    """Calls per device of ops whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in trace["op_count"].items() if rx.search(k))


def summary(directory: str, top: int = 40) -> str:
    """A human-readable look at a trace: planes, lines, and the ops that
    took most device time (``python3 chipbench/trace_reduce.py <dir>``)."""
    from jax.profiler import ProfileData

    path = find_xplane(directory)
    data = ProfileData.from_file(path)
    out = [f"{path} ({os.path.getsize(path)} bytes)"]
    for plane in data.planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name!r}: " + ", ".join(
            f"{ln.name!r}:{sum(1 for _ in ln.events)}" for ln in lines))
        if DEVICE_PLANE.match(plane.name):
            for ln in lines:
                agg = defaultdict(lambda: [0, 0.0])
                first = None
                for ev in ln.events:
                    agg[ev.name][0] += 1
                    agg[ev.name][1] += ev.duration_ns * 1e-9
                    if first is None:
                        first = ev
                out.append(f"  line {ln.name!r}: {len(agg)} names")
                for name, (n, s) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][1])[:top]:
                    out.append(f"    {s:10.6f}s {n:7d}x {name}")
                if first is not None:
                    out.append(f"    stats of {first.name!r}: " + repr(
                        [(k, str(v)[:80]) for k, v in first.stats][:20]))
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(summary(sys.argv[1]))
