"""Readings that set a cell's limits (not part of a benchmark run).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 10] > readings.jsonl

One process builds the cell's programs once and, for every seed, reads the
numbers the cell compares, for the program's timed path against the plain
reference (the lower readings). For every control seed it also reads the
control: the plain reference computed with float8 matrix products in the
program's place (the upper readings), and the faults the cell can have,
planted in the reference put in the program's place. One JSON line per
seed goes to standard output. Needs the chip, like a run.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    harness.setup_environment(ROOT)
    ctx = harness.prepare(ROOT, argparse.Namespace(
        workload=args.workload, seed=seeds[0], seconds=args.seconds,
        trace=0), t_start=T_START)
    devices = harness.require_chips(ctx["cell"]["chips"])
    ctx["devices"] = devices
    driver = harness.load_module(os.path.join(
        HERE, "drivers", f"{ctx['config']['driver']}.py"))
    driver.calibrate(ctx, seeds, control)


if __name__ == "__main__":
    main()
