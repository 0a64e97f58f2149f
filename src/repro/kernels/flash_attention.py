"""TPU Pallas flash attention (forward + recompute backward), AXLearn-style.

TPU-native adaptation of FlashAttention (paper §4.2 dispatches SplashAttention
on TPU): the grid's innermost dimension iterates KV blocks *sequentially*
(TPU grids are sequential in the last axis), carrying the online-softmax
running max / denominator / accumulator in VMEM scratch — the TPU analogue of
a CUDA thread-block's registers/SMEM. Block shapes default to (128, 128) to
align with the 128x128 MXU tile and 8x128 VREG lanes.

Supports: causal masking, sliding windows, logit soft-capping, and GQA
(q-head -> kv-head mapping happens in the BlockSpec index_map so each KV
block is fetched once per group).

Training: :func:`flash_attention` is a ``jax.custom_vjp`` whose backward is
the standard recompute scheme (FlashAttention-2): the forward additionally
emits the per-row logsumexp, and two Pallas passes recompute the probability
blocks from (q, k, lse) instead of materializing the (S, T) matrix —

  * **dKV pass**: grid over KV blocks; for each KV block it streams every
    query block of every q-head in the KV head's GQA group (innermost,
    sequential) and accumulates dK/dV in VMEM scratch.
  * **dQ pass**: grid mirrors the forward; dQ accumulates over KV blocks.

Per-row statistics (the forward's LSE, the backward's LSE and delta) cross
the kernel boundary lane-broadcast, ``(rows, 128)`` f32, so their blocks tile
like any other; the residual saved for the backward is the compact
``(B, Hq, S)`` LSE, broadcast again only for the backward call.

Both passes are GQA- and sliding-window-aware and validated against
``jax.grad`` of ``repro.kernels.ref.reference_attention`` in interpret mode
(CPU), so the flash path is legal under ``jax.grad`` on every platform.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_forward"]

NEG_INF = -1e30
_LANES = 128  # VREG lane count: scratch second-minor dim


def _block_relevant(qi, kj, *, block_q: int, block_k: int, causal: bool,
                    sliding_window: Optional[int]):
    """Whether the (qi, kj) block pair contains any unmasked entry."""
    relevant = True
    if causal:
        relevant = jnp.logical_and(relevant,
                                   kj * block_k <= qi * block_q + block_q - 1)
    if sliding_window is not None:
        relevant = jnp.logical_and(
            relevant, (kj + 1) * block_k - 1 > qi * block_q - sliding_window)
    return relevant


def _pair_mask(q_pos, k_pos, *, q_len: int, kv_len: int, causal: bool,
               sliding_window: Optional[int]):
    """(bq, bk) boolean mask; also masks q/k padding rows/cols."""
    mask = jnp.logical_and(k_pos < kv_len, q_pos < q_len)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if sliding_window is not None:
        mask = jnp.logical_and(mask, k_pos > q_pos - sliding_window)
    return mask


# ---------------------------------------------------------------------------
# Forward kernel (emits per-row logsumexp for the recompute backward)
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # (1, block_q, D)
    k_ref,  # (1, block_k, D)
    v_ref,  # (1, block_k, D)
    o_ref,  # (1, block_q, D)
    lse_ref,  # (1, block_q, _LANES) f32, lane-broadcast
    m_scr,  # (block_q, _LANES) f32
    l_scr,  # (block_q, _LANES) f32
    acc_scr,  # (block_q, D) f32
    *,
    block_q: int,
    block_k: int,
    q_len: int,
    kv_len: int,
    num_kv_blocks: int,
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # Skip fully-masked blocks (beyond the causal frontier / outside window).
    relevant = _block_relevant(qi, kj, block_q=block_q, block_k=block_k,
                               causal=causal, sliding_window=sliding_window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        mask = _pair_mask(q_pos, k_pos, q_len=q_len, kv_len=kv_len,
                          causal=causal, sliding_window=sliding_window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0:1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows: keep exp argument finite.
        p = jnp.exp(s - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - jnp.where(m_new <= NEG_INF / 2, 0.0, m_new))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)  # (bk, D)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        m = m_scr[...]
        lvec = l_scr[...]
        # lse = m + log(l); NEG_INF marks fully-masked (invalid) rows so the
        # backward can zero their probability blocks.
        lse_ref[0] = jnp.where(lvec > 0.0,
                               m + jnp.log(jnp.maximum(lvec, 1e-37)),
                               NEG_INF)


def _fwd_impl(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, T, Hkv, D)
    v: jax.Array,
    *,
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B, S, Hq, D), lse (B, Hq, S) fp32)."""
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, T)

    # Pad sequence dims to block multiples (mask handles the tail).
    S_pad = -(-S // block_q) * block_q
    T_pad = -(-T // block_k) * block_k
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    if T_pad != T:
        k = jnp.pad(k, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))

    # Head-major layout: (B*H, S, D).
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, D)

    num_q_blocks = S_pad // block_q
    num_kv_blocks = T_pad // block_k
    grid = (B * Hq, num_q_blocks, num_kv_blocks)

    def q_index(bh, qi, kj):
        return (bh, qi, 0)

    def kv_index(bh, qi, kj):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, kj, 0)

    def lse_index(bh, qi, kj):
        return (bh, qi, 0)

    kernel = functools.partial(
        _fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        q_len=S,
        kv_len=T,
        num_kv_blocks=num_kv_blocks,
        causal=causal,
        sliding_window=sliding_window,
        logit_softcap=logit_softcap,
        scale=scale,
    )

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_q, _LANES), lse_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, S_pad, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, S_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)

    out = out.reshape(B, Hq, S_pad, D).transpose(0, 2, 1, 3)[:, :S]
    lse = lse[..., 0].reshape(B, Hq, S_pad)[:, :, :S]
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (recompute p from q, k, lse — FlashAttention-2 scheme)
# ---------------------------------------------------------------------------


def _recompute_p_ds(q, k, lse, do, v, delta, mask, *, logit_softcap, scale):
    """Shared recompute: returns (p, ds_raw), both (bq, bk) fp32.

    ``lse``/``delta`` are (bq, 1). Invalid rows carry lse = NEG_INF.
    """
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    row_valid = lse > NEG_INF / 2  # (bq, 1)
    lse_safe = jnp.where(row_valid, lse, 0.0)
    p = jnp.exp(s - lse_safe)
    p = jnp.where(jnp.logical_and(mask, row_valid), p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if logit_softcap is not None:
        ds = ds * (1.0 - jnp.square(s / logit_softcap))
    return p, ds


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,  # (block_q, D) f32
    *,
    block_q: int,
    block_k: int,
    q_len: int,
    kv_len: int,
    num_kv_blocks: int,
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    relevant = _block_relevant(qi, kj, block_q=block_q, block_k=block_k,
                               causal=causal, sliding_window=sliding_window)

    @pl.when(relevant)
    def _compute():
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = _pair_mask(q_pos, k_pos, q_len=q_len, kv_len=kv_len,
                          causal=causal, sliding_window=sliding_window)
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        _, ds = _recompute_p_ds(q, k, lse, do, v, delta, mask,
                                logit_softcap=logit_softcap, scale=scale)
        # dq += scale * ds @ k
        dq_scr[...] = dq_scr[...] + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,  # (block_k, D) f32
    *,
    block_q: int,
    block_k: int,
    q_len: int,
    kv_len: int,
    num_q_blocks: int,
    group_steps: int,  # G * num_q_blocks
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
):
    kj = pl.program_id(1)
    t = pl.program_id(2)  # g * num_q_blocks + qi
    qi = t % num_q_blocks

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    relevant = _block_relevant(qi, kj, block_q=block_q, block_k=block_k,
                               causal=causal, sliding_window=sliding_window)

    @pl.when(relevant)
    def _compute():
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = _pair_mask(q_pos, k_pos, q_len=q_len, kv_len=kv_len,
                          causal=causal, sliding_window=sliding_window)
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        p, ds = _recompute_p_ds(q, k, lse, do, v, delta, mask,
                                logit_softcap=logit_softcap, scale=scale)
        # dv += p^T @ do ; dk += scale * ds^T @ q
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dk_scr[...] = dk_scr[...] + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(t == group_steps - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_impl(
    q, k, v, out, lse, do,
    *,
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
):
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    S_pad = -(-S // block_q) * block_q
    T_pad = -(-T // block_k) * block_k

    def pad_s(x):
        return jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0), (0, 0))) \
            if S_pad != S else x

    def pad_t(x):
        return jnp.pad(x, ((0, 0), (0, T_pad - T), (0, 0), (0, 0))) \
            if T_pad != T else x

    qh = pad_s(q).transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    doh = pad_s(do).transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    oh = pad_s(out).transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    kh = pad_t(k).transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, D)
    vh = pad_t(v).transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, D)

    # delta_i = sum_d do_id * o_id (cheap elementwise preprocess, fp32).
    delta = jnp.sum(doh.astype(jnp.float32) * oh.astype(jnp.float32), axis=-1)
    lse_h = lse.reshape(B * Hq, S)
    if S_pad != S:
        # Padding rows are invalid: lse = NEG_INF zeroes their p blocks.
        lse_h = jnp.pad(lse_h, ((0, 0), (0, S_pad - S)),
                        constant_values=NEG_INF)
    # Lane-broadcast row statistics, (B*Hq, S_pad, _LANES).
    lse_h = jnp.broadcast_to(lse_h[..., None], (*lse_h.shape, _LANES))
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    num_q_blocks = S_pad // block_q
    num_kv_blocks = T_pad // block_k

    def q_index(bh, qi, kj):
        return (bh, qi, 0)

    def kv_index(bh, qi, kj):
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, kj, 0)

    def lse_index(bh, qi, kj):
        return (bh, qi, 0)

    common = dict(
        block_q=block_q, block_k=block_k, q_len=S, kv_len=T,
        causal=causal, sliding_window=sliding_window,
        logit_softcap=logit_softcap, scale=scale,
    )

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_kv_blocks=num_kv_blocks, **common),
        grid=(B * Hq, num_q_blocks, num_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_q, _LANES), lse_index),
            pl.BlockSpec((1, block_q, _LANES), lse_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S_pad, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh, doh, lse_h, delta)

    # dKV pass: one sequential sweep over every (group head, q block) pair
    # per KV block, accumulating in VMEM scratch.
    def kv_self_index(bhkv, kj, t):
        return (bhkv, kj, 0)

    def q_group_index(bhkv, kj, t):
        row = (bhkv // Hkv) * Hq + (bhkv % Hkv) * G + t // num_q_blocks
        return (row, t % num_q_blocks, 0)

    def lse_group_index(bhkv, kj, t):
        row = (bhkv // Hkv) * Hq + (bhkv % Hkv) * G + t // num_q_blocks
        return (row, t % num_q_blocks, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_q_blocks=num_q_blocks,
                          group_steps=G * num_q_blocks, **common),
        grid=(B * Hkv, num_kv_blocks, G * num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_group_index),
            pl.BlockSpec((1, block_k, D), kv_self_index),
            pl.BlockSpec((1, block_k, D), kv_self_index),
            pl.BlockSpec((1, block_q, D), q_group_index),
            pl.BlockSpec((1, block_q, _LANES), lse_group_index),
            pl.BlockSpec((1, block_q, _LANES), lse_group_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), kv_self_index),
            pl.BlockSpec((1, block_k, D), kv_self_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, T_pad, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, T_pad, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh, doh, lse_h, delta)

    dq = dq.reshape(B, Hq, S_pad, D).transpose(0, 2, 1, 3)[:, :S]
    dk = dk.reshape(B, Hkv, T_pad, D).transpose(0, 2, 1, 3)[:, :T]
    dv = dv.reshape(B, Hkv, T_pad, D).transpose(0, 2, 1, 3)[:, :T]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, sliding_window, logit_softcap, scale,
           block_q, block_k, interpret):
    out, _ = _fwd_impl(
        q, k, v, causal=causal, sliding_window=sliding_window,
        logit_softcap=logit_softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _flash_fwd(q, k, v, causal, sliding_window, logit_softcap, scale,
               block_q, block_k, interpret):
    out, lse = _fwd_impl(
        q, k, v, causal=causal, sliding_window=sliding_window,
        logit_softcap=logit_softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sliding_window, logit_softcap, scale, block_q, block_k,
               interpret, residuals, do):
    q, k, v, out, lse = residuals
    dq, dk, dv = _bwd_impl(
        q, k, v, out, lse, do, causal=causal, sliding_window=sliding_window,
        logit_softcap=logit_softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, T, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Differentiable flash attention (Pallas forward + Pallas backward)."""
    D = q.shape[-1]
    scale = (D ** -0.5) if scale is None else scale
    return _flash(q, k, v, causal, sliding_window, logit_softcap, float(scale),
                  block_q, block_k, interpret)


def flash_attention_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Forward-only entry point (serving/prefill hot path)."""
    D = q.shape[-1]
    scale = (D ** -0.5) if scale is None else scale
    out, _ = _fwd_impl(
        q, k, v, causal=causal, sliding_window=sliding_window,
        logit_softcap=logit_softcap, scale=float(scale),
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out
