"""Offered-load sweep of a serving cell (not part of a benchmark run).

    python3 chipbench/sweep.py --workload <cell> --rates 0.3,0.34,0.38 \
        [--seconds 100] [--seed 1]

One process, one engine; for each rate the mix's pre-roll and one window
at that rate, without drain, printing one JSON line: the output tokens per
second against the offered, the running sequences and the backlog (waiting
or still prefilling) at the window's open and close, and time to first
token in the first and second halves of the window. Give windows longer
than a request's lifetime. The knee is the highest rate whose backlog does
not grow over the window.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=100.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    harness.setup_environment(ROOT)
    ctx = harness.prepare(ROOT, argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=0), t_start=T_START)
    ctx["devices"] = harness.require_chips(ctx["cell"]["chips"])
    driver = harness.load_module(os.path.join(
        HERE, "drivers", f"{ctx['config']['driver']}.py"))
    driver.sweep(ctx, [float(r) for r in args.rates.split(",")])


if __name__ == "__main__":
    main()
