"""Row-tiled RMSNorm Pallas kernel (fused normalize + scale).

Memory-bound op: one HBM read + one write per element, fp32 accumulation in
VMEM. Grid tiles rows (tokens); the scale vector is re-fetched per tile (it
lives comfortably in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rmsnorm_forward"]


def _kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (rows, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * scale_ref[0].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_forward(
    x: jax.Array,  # (..., D)
    scale: jax.Array,  # (D,)
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    D = orig_shape[-1]
    n = 1
    for s in orig_shape[:-1]:
        n *= s
    x2 = x.reshape(n, D)
    block_rows = min(block_rows, n)
    n_pad = -(-n // block_rows) * block_rows
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))

    norm = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(n_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="rmsnorm",
    )
    with jax.named_scope("rmsnorm"):
        out = norm(x2, scale.reshape(1, D))
    return out[:n].reshape(orig_shape)
