"""Device time by program scope: the wire-format reader, self time, scope
paths and the layer split, on hand-made events and on traces recorded on
the chip."""

import gzip
import importlib.util
import os
import re
import shutil

import pytest

from chipbench import harness, scopes
from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
# PR 12's program (no module scopes) and this program, both the d8 step.
UNSCOPED = os.path.join(FIXTURES, "train_d8.xplane.pb.gz")
SCOPED = os.path.join(FIXTURES, "train_d8_scopes.xplane.pb.gz")
FLASH = harness.load_module(os.path.join(
    HERE, "metrics", "flash_attn_roofline.train.py")).KERNELS


# ------------------------------------------------------------ self time


def test_self_time_leaves_out_nested_ops():
    ops = [("while", 0.0, 10.0), ("fusion.1", 1.0, 3.0),
           ("kernel", 4.0, 8.0), ("fusion.2", 5.0, 6.0),
           ("copy", 9.0, 9.0), ("next", 10.0, 12.0)]
    out = scopes.self_times(ops, 0.0, 20.0)
    assert out["while"] == pytest.approx([10.0 - 2.0 - 4.0, 1])
    assert out["fusion.1"] == pytest.approx([2.0, 1])
    assert out["kernel"] == pytest.approx([3.0, 1])
    assert out["fusion.2"] == pytest.approx([1.0, 1])
    assert out["copy"] == pytest.approx([0.0, 1])
    assert out["next"] == pytest.approx([2.0, 1])
    total = sum(s for s, _ in out.values())
    assert total == pytest.approx(tr.length(tr.union(
        (a, b) for _, a, b in ops)))


def test_self_time_overlap_and_window():
    # Siblings that overlap: the later one takes the overlap (5 to 6), and
    # each instant is counted once; the window clips every op.
    ops = [("p", 0.0, 10.0), ("a", 2.0, 6.0), ("b", 5.0, 8.0),
           ("tail", 9.0, 14.0), ("late", 15.0, 16.0)]
    out = scopes.self_times(ops, 1.0, 12.0)
    assert out["p"][0] == pytest.approx(2.0)  # 1 to 2 and 8 to 9
    assert out["a"][0] == pytest.approx(3.0)
    assert out["b"][0] == pytest.approx(3.0)
    assert out["tail"][0] == pytest.approx(3.0)
    assert "late" not in out
    assert sum(s for s, _ in out.values()) == pytest.approx(11.0)


# ------------------------------------------------------------ scope paths


@pytest.mark.parametrize("name,path,layer", [
    ("jit(train_step)/transpose(jvp(model))/decoder/stack/while/body/"
     "closed_call/checkpoint/rematted_computation/layer/feed_forward/"
     "up_proj0/dot_general",
     ("model", "decoder", "stack", "layer", "feed_forward", "up_proj0"),
     "feed_forward"),
    ("jit(train_step)/jvp(model)/decoder/stack/while/body/closed_call/layer/"
     "self_attention/flash_fwd/pallas_call",
     ("model", "decoder", "stack", "layer", "self_attention", "flash_fwd"),
     "attention"),
    ("jit(train_step)/layer/self_attention/rope.apply/jit(_where)/select_n",
     ("layer", "self_attention", "rope.apply"), "attention"),
    ("jit(train_step)/transpose(jvp(model))/decoder/emb.attend/dot_general",
     ("model", "decoder", "emb.attend"), "head_loss"),
    ("jit(train_step)/jvp(model)/loss/jit(take_along_axis)/gather",
     ("model", "loss"), "head_loss"),
    ("jit(train_step)/learner/reduce_sum", ("learner",), "learner"),
    ("jit(train_step)/jvp(model)/decoder/final_norm/mul",
     ("model", "decoder", "final_norm"), "other"),
    # PR 12's program: JAX's elements only.
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/dot_general", (), "unscoped"),
    ("jit(train_step)/transpose(jvp(jit(_take)))/scatter-add", (), "unscoped"),
    (None, (), "unscoped"),
])
def test_module_path_and_layer(name, path, layer):
    assert scopes.module_path(name) == path
    assert scopes.layer_of(name) == layer


def test_op_name_of_tf_op():
    assert scopes.op_name("jit(f)/model/dot_general:") == "jit(f)/model/dot_general"
    assert scopes.op_name("jit(f)/model/dot_general") == "jit(f)/model/dot_general"
    assert scopes.op_name(None) is None


def test_split_adds_up_and_is_silent_without_scopes():
    ops = {"a": ("jit(s)/jvp(model)/layer/self_attention/dot_general", 3.0),
           "b": ("jit(s)/jvp(model)/layer/feed_forward/dot_general", 4.0),
           "c": ("jit(s)/transpose(jvp(model))/checkpoint/"
                 "rematted_computation/layer/feed_forward/mul", 1.0),
           "d": ("jit(s)/learner/add", 1.0), "e": (None, 0.5),
           "f": ("jit(s)/jvp(model)/decoder/final_norm/mul", 0.5)}
    reduced = {"self_s": 10.0, "ops": {
        n: {"op_name": o, "self_s": s, "calls": 1.0}
        for n, (o, s) in ops.items()}}
    assert scopes.split(reduced) == pytest.approx({
        "attention": 30.0, "feed_forward": 50.0, "head_loss": 0.0,
        "learner": 10.0, "other": 5.0, "unscoped": 5.0})
    assert scopes.recompute_share(reduced) == pytest.approx(10.0)
    for r in reduced["ops"].values():
        r["op_name"] = "jit(s)/transpose(jvp())/while/body/dot_general"
    assert scopes.split(reduced) is None
    assert scopes.recompute_share(reduced) is None


# ------------------------------------------------------------ wire format


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, msg):
    return _field(1, key) + _field(2, msg)


def test_wire_reader_on_a_hand_made_space():
    """String stats stored inline and by reference to a stat metadata."""
    stat_md = (_field(5, _entry(7, _field(1, 7) + _field(2, b"tf_op")))
               + _field(5, _entry(8, _field(1, 8) + _field(2, b"jit(f)/x/add:")))
               + _field(5, _entry(9, _field(1, 9) + _field(2, b"flops"))))
    ev1 = _field(1, 1) + _field(2, b"%add.1 = f32[] add()") + _field(
        5, _field(1, 7) + _field(7, 8)) + _field(5, _field(1, 9) + _field(4, 12))
    ev2 = _field(1, 2) + _field(2, b"%mul.2") + _field(
        5, _field(1, 7) + _field(5, b"jit(f)/y/mul:"))
    line = _field(2, b"XLA Ops") + _field(4, _field(1, 1) + _field(2, 5))
    device = (_field(1, 3) + _field(2, b"/device:TPU:0") + _field(3, line)
              + _field(4, _entry(1, ev1)) + _field(4, _entry(2, ev2))
              + stat_md)
    host = _field(2, b"/host:CPU") + _field(4, _entry(1, _field(2, b"span")))
    space = _field(1, device) + _field(1, host) + _field(4, b"host-0")
    assert scopes.tf_ops(space) == {"/device:TPU:0": {
        "%add.1 = f32[] add()": "jit(f)/x/add:", "%mul.2": "jit(f)/y/mul:"}}


def _xplane_pb2():
    """TensorFlow's generated ``xplane_pb2``, loaded by path (importing
    TensorFlow itself takes seconds); None where it is not installed."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    if not os.path.exists(path):
        return None
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    try:
        mod_spec.loader.exec_module(mod)
    except Exception:  # noqa: BLE001 — a protobuf runtime it cannot use
        return None
    return mod


def _unzipped(tmp_path_factory, fixture):
    assert os.path.getsize(fixture) < 1 << 20
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(fixture, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def scoped_path(tmp_path_factory):
    """Three steps of the d8 training cell with this program's scopes,
    traced on a TPU v5e; gzip keeps the committed file under 1 MB."""
    return _unzipped(tmp_path_factory, SCOPED)


@pytest.fixture(scope="module")
def unscoped_path(tmp_path_factory):
    return _unzipped(tmp_path_factory, UNSCOPED)


@pytest.mark.parametrize("which", ["scoped", "unscoped"])
def test_wire_reader_matches_xplane_pb2(which, request):
    pb2 = _xplane_pb2()
    if pb2 is None:
        pytest.skip("TensorFlow's xplane_pb2 is not installed")
    path = request.getfixturevalue(f"{which}_path")
    with open(path, "rb") as f:
        data = f.read()
    space = pb2.XSpace()
    space.ParseFromString(data)
    want = {}
    for plane in space.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        events = want.setdefault(plane.name, {})
        for md in plane.event_metadata.values():
            events[md.name] = None
            for st in md.stats:
                if names[st.metadata_id] != "tf_op":
                    continue
                kind = st.WhichOneof("value")
                events[md.name] = (st.str_value if kind == "str_value"
                                   else names[st.ref_value])
    assert any(v for evs in want.values() for v in evs.values())
    assert scopes.tf_ops(data) == want


# ------------------------------------------------------------ recorded


@pytest.fixture(scope="module")
def scoped(scoped_path):
    host, devices = tr.load_events(scoped_path)
    return host, devices, scopes.reduce_file(scoped_path,
                                             window="bench.window")


def test_recorded_self_time_is_busy_time(scoped):
    host, devices, red = scoped
    out = tr.reduce_events(host, devices, window="bench.window")
    assert red["self_s"] == pytest.approx(out["busy_s"])
    assert 0.9 < out["busy_s"] / out["window_s"] < 1.0


def test_recorded_flash_scopes_are_the_flash_kernels(scoped):
    """The kernels' own scopes pick out exactly the ops that the flash
    metrics' HLO-text patterns match, with the same calls."""
    _, _, red = scoped
    for kind, pattern in FLASH.items():
        rx = re.compile(pattern)
        by_regex = {n: r["calls"] for n, r in red["ops"].items()
                    if rx.search(n)}
        by_scope = {n: r["calls"] for n, r in red["ops"].items()
                    if f"flash_{kind}" in scopes.module_path(r["op_name"])}
        assert by_regex and by_scope == by_regex, kind
    calls = {k: sum(r["calls"] for r in red["ops"].values()
                    if f"flash_{k}" in scopes.module_path(r["op_name"]))
             for k in FLASH}
    # 3 steps x 8 layers; the forward runs twice under full remat.
    assert calls == {"fwd": 48, "dq": 24, "dkv": 24}


def test_recorded_split(scoped):
    _, _, red = scoped
    split = scopes.split(red)
    assert sum(split.values()) == pytest.approx(100.0)
    assert split["unscoped"] < 5.0
    for layer in scopes.LAYERS:
        assert split[layer] > 1.0, layer
    assert 5.0 < scopes.recompute_share(red) < 30.0


def test_recorded_without_scopes_is_silent(unscoped_path):
    red = scopes.reduce_file(unscoped_path, window="bench.window")
    assert red["self_s"] > 0
    assert scopes.split(red) is None
    assert scopes.recompute_share(red) is None
