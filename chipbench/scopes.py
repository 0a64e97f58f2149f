"""Device time by the program's own names: the scope path of every op.

Each module invocation runs under a ``jax.named_scope`` (the module's name,
or ``<name>.<method>``), and a few pieces of work that no module owns have
explicit scopes (``learner``, ``loss``, one per Pallas call site). XLA keeps
the name stack as each op's ``op_name``, and the profiler keeps that as the
``tf_op`` stat of the op's event metadata in the ``.xplane.pb``. JAX's
``ProfileData`` does not expose metadata stats, so the few fields needed
are read here from the protobuf wire format, with the field numbers of
``XSpace``/``XPlane``/``XEventMetadata``/``XStat`` in the profiler's
``xplane.proto``; TensorFlow is not imported.

An op's time is its *self time* inside the window: its interval minus the
ops nested in it on the same line, so a ``while`` op does not count the
body ops it contains and the self times of a device add up to its busy
time. Times are averaged over the devices traced.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench.trace_reduce import DEVICE_PLANE, find_xplane, load_events

# Field numbers (xplane.proto).
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
EVENT_METADATA_NAME, EVENT_METADATA_STATS = 2, 5
STAT_METADATA_NAME = 2
XSTAT_METADATA_ID, XSTAT_STR, XSTAT_REF = 1, 5, 7

TF_OP = "tf_op"

# Name-stack elements that JAX makes, not the program: the step's and
# library functions' ``jit(...)``, the scan's loop, remat, einsum
# subscripts. A transform (``jvp``, ``transpose``, ``vmap``) wraps the
# element it applies to, e.g. ``transpose(jvp(model))``.
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_JAX_ELEMENT = re.compile(
    r"^(?:jit\(.*\)|while|body|cond|closed_call|checkpoint"
    r"|rematted_computation|.*->.*)?$")


# ------------------------------------------------------------ wire format


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, ``bytes`` for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = bytes(buf[i:i + size]), i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire} (field {num})")
        yield num, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for num, v in fields(buf):
        if num == MAP_KEY:
            key = v
        elif num == MAP_VALUE:
            value = v
    return key, value


def _tf_op(md: bytes, stat_names: Dict[int, str]
           ) -> Tuple[str, Optional[str]]:
    """(name, ``tf_op`` stat or None) of one ``XEventMetadata``; the stat's
    string is stored inline or as a reference to a stat metadata."""
    name, tf_op = "", None
    for num, v in fields(md):
        if num == EVENT_METADATA_NAME:
            name = v.decode("utf-8", "replace")
        elif num == EVENT_METADATA_STATS:
            key, text = None, None
            for snum, sv in fields(v):
                if snum == XSTAT_METADATA_ID:
                    key = stat_names.get(sv)
                elif snum == XSTAT_STR:
                    text = sv.decode("utf-8", "replace")
                elif snum == XSTAT_REF:
                    text = stat_names.get(sv)
            if key == TF_OP:
                tf_op = text
    return name, tf_op


def tf_ops(data: bytes) -> Dict[str, Dict[str, Optional[str]]]:
    """{device plane: {event name: ``tf_op`` stat or None}} of an
    ``XSpace``."""
    out = {}
    for num, pbuf in fields(data):
        if num != XSPACE_PLANES:
            continue
        name, metas, stat_names = "", [], {}
        for pnum, v in fields(pbuf):
            if pnum == XPLANE_NAME:
                name = v.decode("utf-8", "replace")
            elif pnum == XPLANE_EVENT_METADATA:
                metas.append(v)
            elif pnum == XPLANE_STAT_METADATA:
                key, sm = _map_entry(v)
                for snum, sv in fields(sm):
                    if snum == STAT_METADATA_NAME:
                        stat_names[key] = sv.decode("utf-8", "replace")
        if not DEVICE_PLANE.match(name):
            continue
        out[name] = dict(_tf_op(_map_entry(entry)[1], stat_names)
                         for entry in metas)
    return out


# ------------------------------------------------------------ scope paths


def op_name(tf_op: Optional[str]) -> Optional[str]:
    """The HLO ``op_name`` of a ``tf_op`` stat (``<op_name>:<op_type>``)."""
    if not tf_op:
        return None
    return tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op


def module_path(name: Optional[str]) -> Tuple[str, ...]:
    """The program's own scopes in an ``op_name``, outermost first: JAX's
    elements and the primitive (the last element) left out."""
    if not name:
        return ()
    out = []
    for element in name.split("/")[:-1]:
        m = _TRANSFORM.match(element)
        while m:
            element = m.group(1)
            m = _TRANSFORM.match(element)
        if not _JAX_ELEMENT.match(element):
            out.append(element)
    return tuple(out)


# ------------------------------------------------------------ self time


def self_times(ops: Sequence[Tuple[str, float, float]], lo: float, hi: float
               ) -> Dict[str, List[float]]:
    """{event name: [self seconds, calls]} of the ops of one line, clipped
    to ``[lo, hi]``. Each instant goes to the innermost op running then
    (the last one to start), so an op's self time leaves out the ops nested
    in it, and the self times add up to the union of the intervals."""
    clipped = sorted((max(a, lo), -min(b, hi), n) for n, a, b in ops
                     if b > lo and a < hi)
    out: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    stack: List[Tuple[str, float]] = []  # (name, end) in order of start
    t = clipped[0][0] if clipped else lo

    def advance(until: float):
        nonlocal t
        while stack and t < until:
            name, end = stack[-1]
            if end <= t:
                stack.pop()
                continue
            step = min(end, until)
            out[name][0] += step - t
            t = step
        t = max(t, until)

    for a, neg_b, n in clipped:
        advance(a)
        stack.append((n, -neg_b))
        out[n][1] += 1
    advance(hi)
    return dict(out)


def reduce_events(host, devices, names: Dict[str, Dict[str, Optional[str]]],
                  *, window: str) -> Dict:
    """{"self_s": busy self time, "ops": {event name: {"op_name", "self_s",
    "calls"}}}, averaged over the devices."""
    if not host.get(window):
        raise ValueError(f"no host span {window!r} in the trace")
    lo = min(a for a, _ in host[window])
    hi = max(b for _, b in host[window])
    n_dev = max(len(devices), 1)
    ops: Dict[str, Dict] = {}
    for plane in sorted(devices):
        tf_op = names.get(plane, {})
        for n, (s, calls) in self_times(devices[plane], lo, hi).items():
            rec = ops.setdefault(n, {"op_name": op_name(tf_op.get(n)),
                                     "self_s": 0.0, "calls": 0.0})
            rec["self_s"] += s / n_dev
            rec["calls"] += calls / n_dev
    return {"self_s": sum(r["self_s"] for r in ops.values()), "ops": ops}


def reduce_file(path: str, *, window: str) -> Dict:
    host, devices = load_events(path)
    with open(path, "rb") as f:
        names = tf_ops(f.read())
    return reduce_events(host, devices, names, window=window)


def for_run(ctx) -> Dict:
    """The scope reduction of a traced run's window, read once per run and
    kept in the run's context for the other readers."""
    if "scopes" not in ctx:
        from chipbench import harness

        ctx["scopes"] = reduce_file(find_xplane(harness.trace_dir(ctx)),
                                    window="bench.window")
    return ctx["scopes"]


# ------------------------------------------------------------ shares

# The layers busy time is split into. An op belongs to the layer named by
# the innermost of its program scopes that names one; an op under program
# scopes that name none is "other" (norms, the embedding, the layer scan's
# own ops), one under no program scope is "unscoped".
LAYERS = {
    "attention": ("self_attention",),
    "feed_forward": ("feed_forward",),
    "head_loss": ("emb.attend", "lm_head", "decoder.head", "loss"),
    "learner": ("learner",),
}
_LAYER_OF_SCOPE = {s: layer for layer, names in LAYERS.items() for s in names}


def layer_of(name: Optional[str]) -> str:
    """The layer of an op with this ``op_name`` (a key of ``LAYERS``,
    "other" or "unscoped")."""
    path = module_path(name)
    if not path:
        return "unscoped"
    for scope in reversed(path):
        if scope in _LAYER_OF_SCOPE:
            return _LAYER_OF_SCOPE[scope]
    return "other"


def recomputed(name: Optional[str]) -> bool:
    """Whether an op is remat's recomputation (any layer)."""
    return bool(name) and "rematted_computation" in name.split("/")


def split(reduced: Dict) -> Optional[Dict[str, float]]:
    """Percent of the busy self time by layer (``LAYERS``, "other",
    "unscoped"; they add up to 100), or None where no op carries a program
    scope: a program that opens none."""
    ops = reduced["ops"].values()
    if not reduced["self_s"] or not any(module_path(r["op_name"])
                                        for r in ops):
        return None
    out = {k: 0.0 for k in (*LAYERS, "other", "unscoped")}
    for r in ops:
        out[layer_of(r["op_name"])] += 100.0 * r["self_s"] / reduced["self_s"]
    return out


def recompute_share(reduced: Dict) -> Optional[float]:
    """Percent of the busy self time in remat's recomputation."""
    if split(reduced) is None:
        return None
    spent = sum(r["self_s"] for r in reduced["ops"].values()
                if recomputed(r["op_name"]))
    return 100.0 * spent / reduced["self_s"]
