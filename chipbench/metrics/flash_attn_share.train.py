"""Share of the device's busy time spent in the flash-attention kernels."""

import os

from chipbench import harness
from chipbench.trace_reduce import ops_matching

PATTERN = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "flash_attn_roofline.train.py")).PATTERN


def read(ctx):
    tr = ctx["trace"]
    spent = ops_matching(tr, PATTERN)
    if not spent or not tr["busy_s"]:
        return None
    return 100.0 * spent / tr["busy_s"]
