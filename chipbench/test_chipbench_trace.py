"""The reduction from a profiler trace to device metrics, on hand-made
events and on a small trace recorded on the chip."""

import gzip
import os
import shutil

import pytest

from chipbench import harness
from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "train_d8.xplane.pb.gz")
FLASH = harness.load_module(os.path.join(
    HERE, "metrics", "flash_attn_roofline.train.py")).KERNELS

HOST = {"bench.window": [(0.5, 4.0)], "train.input": [(1.0, 1.6)],
        "train.fetch": [(3.5, 4.0)], "other": [(0.0, 9.0)]}
OPS = [("fusion.1", 0.0, 1.0), ("_fwd_kernel", 1.5, 2.0),
       ("all-reduce.3", 2.5, 3.5), ("fusion.2", 3.0, 3.2),
       ("late", 4.5, 5.0)]


def test_busy_idle_and_op_time():
    out = tr.reduce_events(HOST, {"/device:TPU:0": OPS}, window="bench.window",
                           annotations=("train.input", "train.fetch"))
    assert out["window_s"] == pytest.approx(3.5)
    # (0.5, 1.0) + (1.5, 2.0) + (2.5, 3.5): clipped to the window.
    assert out["busy_s"] == pytest.approx(2.0)
    assert out["idle_share"] == pytest.approx(1.5 / 3.5)
    assert out["op_time"]["fusion.1"] == pytest.approx(0.5)
    assert "late" not in out["op_time"]
    assert tr.ops_matching(out, r"_fwd_kernel") == pytest.approx(0.5)
    assert tr.calls_matching(out, r"fusion") == 2


def test_idle_gaps_are_attributed_to_host_spans():
    out = tr.reduce_events(HOST, {"/device:TPU:0": OPS}, window="bench.window",
                           annotations=("train.input", "train.fetch"))
    idle = dict(out["idle_by_host"])
    # Gaps (1.0, 1.5) under train.input, (2.0, 2.5) under nothing,
    # (3.5, 4.0) under train.fetch.
    assert idle == pytest.approx({"train.input": 0.5, "(none)": 0.5,
                                  "train.fetch": 0.5})


def test_collective_exposed_time():
    out = tr.reduce_events(HOST, {"/device:TPU:0": OPS}, window="bench.window")
    assert out["collective_s"] == pytest.approx(1.0)
    # fusion.2 overlaps 0.2 s of the all-reduce.
    assert out["collective_exposed_s"] == pytest.approx(0.8)


def test_devices_are_averaged():
    two = {"/device:TPU:0": OPS, "/device:TPU:1": [("fusion.9", 0.5, 4.0)]}
    out = tr.reduce_events(HOST, two, window="bench.window")
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((2.0 + 3.5) / 2)
    assert out["collective_exposed_s"] == pytest.approx(0.8 / 2)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Ten steps of the d8 training cell, traced on a TPU v5e (3 s
    window); gzip keeps the committed file under 1 MB."""
    assert os.path.getsize(FIXTURE) < 1 << 20
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.load_events(str(path))


def test_recorded_busy_and_idle(recorded):
    host, devices = recorded
    assert list(devices) == ["/device:TPU:0"]
    names = ("train.input", "train.dispatch", "train.fetch")
    out = tr.reduce_events(host, devices, window="bench.window",
                           annotations=names)
    (lo, hi), = host["bench.window"]
    assert out["window_s"] == pytest.approx(hi - lo)
    u = tr.union((a, b) for _, a, b in devices["/device:TPU:0"])
    assert out["busy_s"] == pytest.approx(tr.length(
        (max(a, lo), min(b, hi)) for a, b in u if b > lo and a < hi))
    assert 0.9 < out["busy_s"] / out["window_s"] < 1.0
    idle = sum(v for _, v in out["idle_by_host"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"])
    # The loop blocks on each step's loss: most idle time is the fetch.
    assert out["idle_by_host"][0][0] == "train.fetch"
    assert out["collective_s"] == 0


def test_recorded_kernel_time(recorded):
    host, devices = recorded
    out = tr.reduce_events(host, devices, window="bench.window")
    calls = {k: tr.calls_matching(out, p) for k, p in FLASH.items()}
    # 10 steps x 8 layers; the forward runs twice under full remat.
    assert calls == {"fwd": 160, "dq": 80, "dkv": 80}
    spent = {k: tr.ops_matching(out, p) for k, p in FLASH.items()}
    assert all(0 < v for v in spent.values())
    assert 0.1 < sum(spent.values()) / out["busy_s"] < 0.5
