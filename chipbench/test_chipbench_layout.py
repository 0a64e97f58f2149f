"""The benchmark's files are found by name, and a run refuses the CPU."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from chipbench import harness, tiny

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
ALL = tiny.bench()  # the listed cells and the pending ones
CELLS = [w["name"] for w in ALL["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_sources():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert {m["source"] for m in BENCH["end_to_end"]} <= {"host_clock",
                                                         "device_trace"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(m["workloads"] for m in BENCH["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    """Config, mix, driver, limits and one reader per metric exist for
    every cell, and the cell reports setup_s, another end-to-end metric
    and a per-layer metric."""
    for trace in (0, 1):
        ctx = harness.prepare(ROOT, types.SimpleNamespace(
            workload=cell, seed=1, seconds=1, trace=trace), t_start=0.0,
            bench=ALL)
        assert os.path.exists(os.path.join(
            HERE, "drivers", f"{ctx['config']['driver']}.py"))
        assert os.path.exists(os.path.join(
            HERE, "configs", f"{ctx['config']['reference']}.py"))
        assert ctx["limits"], f"no limits for {cell}"
        names = [m["name"] for m in ctx["metrics"]]
        if trace:
            assert names
        else:
            assert "setup_s" in names and len(names) >= 2
        for name in names:
            mod = harness.load_module(os.path.join(HERE, "metrics",
                                                   f"{name}.py"))
            assert callable(mod.read)


@pytest.mark.parametrize("entry", ALL["configs"], ids=lambda c: c["name"])
def test_config_reduced_keys_are_the_only_changes(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert cfg["source"] == entry["source"]
    for key in ("assumed", "deployment", "chips"):
        assert key in cfg
    assert cfg["hidden_size"] == 1536 and cfg["intermediate_size"] == 8960
    assert cfg["num_attention_heads"] == 12
    assert cfg["num_key_value_heads"] == 2
    assert cfg["vocab_size"] == 151936
    assert ("num_hidden_layers" in entry["reduced"]) == (
        cfg["num_hidden_layers"] != 28)


def _run_cpu(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "5", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_refuses_the_cpu():
    out = _run_cpu(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_needs_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and chipbench/ gives no
    result either."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cpu(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
