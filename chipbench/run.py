"""Runs one cell of the benchmark once and prints one JSON result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration file,
its traffic mix (``chipbench/traffic/<mix>.json``), its driver
(``chipbench/drivers/<driver>.py``, named by the configuration), its limits
(``chipbench/limits/<cell>.json``) and one reader per metric
(``chipbench/metrics/<metric>.py``) are all found by name. Nothing here
knows a cell.

The run loads, warms up every shape it will use (set-up), measures for
``--seconds``, and then checks what the timed path produced against the
plain reference. ``--trace 1`` profiles the measured window and reports the
per-layer metrics instead of the end-to-end ones. Without a TPU with as many
chips as the cell asks for it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import harness, peaks  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    harness.setup_environment(ROOT)
    ctx = harness.prepare(ROOT, args, t_start=T_START)
    devices = harness.require_chips(ctx["cell"]["chips"])
    ctx["peaks"] = peaks.peaks_for(devices[0].device_kind)
    result = harness.run_cell(ctx, devices)
    harness.emit(result)


if __name__ == "__main__":
    main()
