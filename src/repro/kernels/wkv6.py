"""Chunked WKV6 (RWKV6 core) Pallas TPU kernel.

TPU adaptation of the RWKV6 CUDA kernel: instead of one-thread-per-channel
serial recurrence, the sequence is chunked; within a chunk everything is
dense (K x K / K x V) matmul work for the MXU, and the (H, K, V) state is
carried across the sequential chunk grid dimension in VMEM scratch — the
same carry pattern as the flash-attention kernel.

Grid: (B * H, num_chunks) with the chunk axis sequential ("arbitrary").
Per (b, h, chunk):
  logw        = log w (chunk, K)           decay logs
  cum/cum_ex  = inclusive/exclusive prefix sums (a lower-triangular ones
                matmul at full f32 precision: Mosaic has no cumsum)
  o = (r * e^{cum_ex}) @ s                           state contribution
    + tril_strict((r e^{cum_ex - mid}) (k e^{mid - cum})^T) @ v   intra-chunk
    + ((r*u) . k) v                                   current-token bonus
  s = e^{total} * s + (k e^{total - cum})^T @ v       state update

A sequence that is not a whole number of chunks is padded with identity
steps (w = 1, k = v = r = 0), which leave the state and the real outputs
unchanged.

Forward only (training uses the chunked jnp form which autodiffs); decode
uses the O(1) recurrent step. Validated in interpret mode vs
``ref.reference_wkv6_recurrent``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_forward"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_out_ref,
            s_scr, *, chunk: int, num_chunks: int, num_heads: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (C, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)  # (C, V)
    w = w_ref[0].astype(jnp.float32)  # (C, K), in (0, 1)
    u = u_ref[0].astype(jnp.float32)  # (1, K) -> broadcast

    logw = jnp.log(jnp.maximum(w, 1e-20))
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (cols <= rows).astype(jnp.float32)
    cum = jax.lax.dot_general(  # inclusive prefix sum over the chunk
        tril, logw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    cum_ex = cum - logw  # exclusive
    total = cum[chunk - 1:]  # (1, K)
    mid = cum[chunk // 2:chunk // 2 + 1]  # (1, K) re-centering for fp32 range

    s = s_scr[...]  # (K, V)
    # state contribution
    r_dec = r * jnp.exp(cum_ex)
    o = jax.lax.dot_general(r_dec, s, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk (strictly causal)
    ri = r * jnp.exp(cum_ex - mid)
    kj = k * jnp.exp(mid - cum)
    att = jax.lax.dot_general(ri, kj, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (C, C)
    att = jnp.where(cols < rows, att, 0.0)
    o = o + jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # current-token bonus
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)  # (C, 1)
    o = o + bonus * v
    o_ref[0] = o.astype(o_ref.dtype)

    # state update
    k_dec = k * jnp.exp(total - cum)
    s_new = jnp.exp(total).T * s + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    @pl.when(ci == num_chunks - 1)
    def _finalize():
        s_out_ref[0] = s_new


def wkv6_forward(
    r: jax.Array,  # (B, T, H, K)
    k: jax.Array,
    v: jax.Array,  # (B, T, H, V)
    w: jax.Array,  # (B, T, H, K)
    u: jax.Array,  # (H, K)
    state: Optional[jax.Array] = None,  # (B, H, K, V)
    *,
    chunk_size: int = 64,
    interpret: bool = False,
):
    B, T_in, H, K = r.shape
    V = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)
    C = chunk_size
    T = -(-T_in // C) * C
    if T != T_in:
        # Identity steps: decay 1 and zero k/v/r leave the state as it was.
        pad = ((0, 0), (0, T - T_in), (0, 0), (0, 0))
        r, k, v = (jnp.pad(x, pad) for x in (r, k, v))
        w = jnp.pad(w, pad, constant_values=1)
    n_chunks = T // C

    # Head-major: (B*H, T, *); chunk index becomes the sequential grid dim.
    def hm(x, d):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, d)

    rh, kh, wh = hm(r, K), hm(k, K), hm(w, K)
    vh = hm(v, V)
    sh = state.reshape(B * H, K, V)
    uh = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    grid = (B * H, n_chunks)

    def seq_index(bh, ci):
        return (bh, ci, 0)

    def head_index(bh, ci):
        return (bh, 0, 0)

    kernel = functools.partial(_kernel, chunk=C, num_chunks=n_chunks,
                               num_heads=H)
    wkv = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, K), seq_index),
            pl.BlockSpec((1, C, K), seq_index),
            pl.BlockSpec((1, C, V), seq_index),
            pl.BlockSpec((1, C, K), seq_index),
            pl.BlockSpec((1, 1, K), head_index),
            pl.BlockSpec((1, K, V), head_index),
        ],
        out_specs=[
            pl.BlockSpec((1, C, V), seq_index),
            pl.BlockSpec((1, K, V), head_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, V), r.dtype),
            jax.ShapeDtypeStruct((B * H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="wkv6",
    )
    with jax.named_scope("wkv6"):
        out, s_out = wkv(rh, kh, vh, wh, uh, sh)

    out = out.reshape(B, H, T, V).transpose(0, 2, 1, 3)[:, :T_in]
    return out, s_out.reshape(B, H, K, V)
