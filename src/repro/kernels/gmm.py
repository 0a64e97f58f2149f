"""Grouped matrix product over the experts a layer holds: the Pallas
``megablox`` kernel that ships with JAX.

``lhs`` (m, k) holds the rows of every group one after another, group i's
``group_sizes[i]`` rows first; rows past the last group belong to none. The
kernel's grid covers only the row tiles that hold a group's rows (the tile
count is computed on the device from ``group_sizes``), so its work follows
the rows routed to the groups, not ``m``; the rows past the groups are left
unwritten, and callers mask them. Its custom VJP computes the input
gradient with the same kernel and the weight gradient with ``tgmm``.
"""

from __future__ import annotations

import jax

__all__ = ["grouped_matmul", "tiling_for"]

# v5e tiles (rows, contraction, output columns), chosen on the chip at the
# expert shapes of the Moonlight cell (PERF.md, Findings): a whole 2048 or
# 1408 contraction per step.
TILE_M, TILE_K, TILE_N = 512, 2048, 512


def tiling_for(m: int, k: int, n: int):
    """(tm, tk, tn): the table's tiles, cut to the shape; tm must divide
    ``m`` (k and n may end in a partial tile)."""
    tm = TILE_M
    while m % tm:
        tm //= 2
    return tm, min(TILE_K, k), min(TILE_N, n)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, interpret: bool = False) -> jax.Array:
    """(m, k) x (groups, k, n) -> (m, n) in ``lhs``'s dtype, f32
    accumulation."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    m, k = lhs.shape
    return megablox.gmm(lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype,
                        tiling_for(m, k, rhs.shape[-1]), None, None,
                        interpret=interpret)
