"""TPU Pallas flash attention (forward + recompute backward), AXLearn-style.

TPU-native adaptation of FlashAttention (paper §4.2 dispatches SplashAttention
on TPU): the grid's innermost dimension iterates KV blocks *sequentially*
(TPU grids are sequential in the last axis), carrying the online-softmax
running max / denominator / accumulator in VMEM scratch — the TPU analogue of
a CUDA thread-block's registers/SMEM. Block shapes default to (128, 128) to
align with the 128x128 MXU tile and 8x128 VREG lanes.

Supports: causal masking, sliding windows, logit soft-capping, GQA, and a
value head dim Dv that differs from the q/k head dim D (latent attention:
D = 192, Dv = 128). The
forward and dQ grids run over query heads (B*Hq) and map each to its KV head
in the BlockSpec index_map, so a KV block is fetched once per query head, not
once per group; the dKV grid runs over KV heads and streams its group's
queries.

Every (q block, kv block) pair of a head falls in one of three classes, read
from the grid position, the causal flag, the window and the unpadded lengths
(:class:`_Blocks`, counted by :func:`block_census`):

  * **empty**: no score is kept. The body does not run, and the index maps
    point the step at the block the next computing step needs, so the
    pipeline copies nothing for it.
  * **partial**: some scores are kept. The body builds the element mask.
  * **full**: every score is kept, with no padded row or column. The body
    builds no mask: its selects and guards would all be identities.

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

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_forward", "block_census"]

NEG_INF = -1e30
_LANES = 128  # VREG lane count: scratch second-minor dim


def _and(a, b):
    """Logical and that stays a Python bool while both sides are static."""
    if a is True or b is False:
        return b
    if b is True or a is False:
        return a
    return jnp.logical_and(a, b)


def _not(a):
    return (not a) if isinstance(a, bool) else jnp.logical_not(a)


@dataclasses.dataclass(frozen=True)
class _Blocks:
    """The block tiling of a (q_len, kv_len) score matrix, and which scores
    are kept: (r, c) is kept iff r < q_len, c < kv_len, c <= r when causal
    and c > r - sliding_window when a window is set.

    :meth:`classify` is the one predicate the kernels' bodies, their index
    maps and :func:`block_census` read.
    """

    block_q: int
    block_k: int
    q_len: int
    kv_len: int
    causal: bool
    sliding_window: Optional[int]

    @classmethod
    def of(cls, S, T, block_q, block_k, causal, sliding_window):
        return cls(min(block_q, S), min(block_k, T), S, T, causal,
                   sliding_window)

    @property
    def num_q_blocks(self) -> int:
        return -(-self.q_len // self.block_q)

    @property
    def num_kv_blocks(self) -> int:
        return -(-self.kv_len // self.block_k)

    def classify(self, qi, kj):
        """(some score kept, every score kept) for the pair (qi, kj).

        Each is a Python bool where it holds for every pair, else a traced
        (or, from :func:`block_census`, an array) boolean.
        """
        q_first, k_first = qi * self.block_q, kj * self.block_k
        q_last = q_first + self.block_q - 1
        k_last = k_first + self.block_k - 1
        some, every = True, True
        # Padding rows and columns (only in the last block) are never kept;
        # the bounds below are those of the unpadded rows and columns.
        if self.q_len % self.block_q:
            every = _and(every, q_last < self.q_len)
            q_last = jnp.minimum(q_last, self.q_len - 1)
        if self.kv_len % self.block_k:
            every = _and(every, k_last < self.kv_len)
            k_last = jnp.minimum(k_last, self.kv_len - 1)
        # r - c spans [q_first - k_last, q_last - k_first] in the block.
        if self.causal:  # kept iff r - c >= 0
            some = _and(some, k_first <= q_last)
            every = _and(every, k_last <= q_first)
        if self.sliding_window is not None:  # kept iff r - c < window
            some = _and(some, k_last > q_first - self.sliding_window)
            every = _and(every, k_first > q_last - self.sliding_window)
        return some, every

    def mask(self, qi, kj):
        """(block_q, block_k) mask of the kept scores of a partial pair."""
        shape = (self.block_q, self.block_k)
        q_pos = qi * self.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = kj * self.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = True
        if self.q_len % self.block_q:
            mask = _and(mask, q_pos < self.q_len)
        if self.kv_len % self.block_k:
            mask = _and(mask, k_pos < self.kv_len)
        if self.causal:
            mask = _and(mask, k_pos <= q_pos)
        if self.sliding_window is not None:
            mask = _and(mask, k_pos > q_pos - self.sliding_window)
        return mask

    def first_kv_block(self, qi):
        """The first kv block that q block ``qi`` needs (the window's edge)."""
        if self.sliding_window is None:
            return 0
        first_col = jnp.maximum(qi * self.block_q - self.sliding_window + 1, 0)
        return jnp.minimum(first_col // self.block_k, self.num_kv_blocks - 1)

    def first_q_block(self, kj):
        """The first q block that kv block ``kj`` needs (the causal edge)."""
        if not self.causal:
            return 0
        return jnp.minimum(kj * self.block_k // self.block_q,
                           self.num_q_blocks - 1)


def _run_blocks(blocks: _Blocks, qi, kj, body):
    """Runs ``body(masked=True)`` on a partial pair, ``body(masked=False)``
    on a full one, and nothing on an empty one."""
    some, every = blocks.classify(qi, kj)
    pl.when(_and(some, _not(every)))(functools.partial(body, True))
    pl.when(every)(functools.partial(body, False))


def block_census(S: int, T: int, block_q: int, block_k: int,
                 causal: bool = True,
                 sliding_window: Optional[int] = None) -> Dict[str, int]:
    """How many (q block, kv block) pairs of one head's grid are empty,
    partial and full, by the predicate the kernels run."""
    blocks = _Blocks.of(S, T, block_q, block_k, causal, sliding_window)
    shape = (blocks.num_q_blocks, blocks.num_kv_blocks)
    some, every = blocks.classify(np.arange(shape[0])[:, None],
                                  np.arange(shape[1])[None, :])
    some = int(np.broadcast_to(np.asarray(some), shape).sum())
    every = int(np.broadcast_to(np.asarray(every), shape).sum())
    return {"empty": shape[0] * shape[1] - some, "partial": some - every,
            "full": every}


def _kv_index_map(blocks: _Blocks, Hq: int, Hkv: int):
    """K/V index map of the (B*Hq, q block, kv block) grids (forward, dQ).

    An empty step points at the block the next computing step reads: below
    the window, its row's first needed block; past the causal edge, the next
    row's (the last row's own, at the end of a head). Equal consecutive
    indices are not copied again.
    """
    G = Hq // Hkv

    def index(bh, qi, kj):
        some, _ = blocks.classify(qi, kj)
        if some is not True:
            first = blocks.first_kv_block(qi)
            after = blocks.first_kv_block(
                jnp.minimum(qi + 1, blocks.num_q_blocks - 1))
            kj = jnp.where(some, kj, jnp.where(kj < first, first, after))
        b, h = bh // Hq, bh % Hq
        return (b * Hkv + h // G, kj, 0)

    return index


def _q_group_index_map(blocks: _Blocks, Hq: int, Hkv: int):
    """q / dO / LSE / delta index map of the dKV grid
    (B*Hkv, kv block, group head * q block).

    An empty step points at the first q block this kv block needs: of this
    group head before the causal edge, of the next one past the window (of
    this one, for the last group head).
    """
    G = Hq // Hkv
    nq = blocks.num_q_blocks

    def index(bhkv, kj, t):
        g, qi = t // nq, t % nq
        some, _ = blocks.classify(qi, kj)
        if some is not True:
            first = blocks.first_q_block(kj)
            g = jnp.where(jnp.logical_or(some, qi < first), g,
                          jnp.minimum(g + 1, G - 1))
            qi = jnp.where(some, qi, first)
        return ((bhkv // Hkv) * Hq + (bhkv % Hkv) * G + g, qi, 0)

    return index


# ---------------------------------------------------------------------------
# Forward kernel (emits per-row logsumexp for the recompute backward)
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # (1, block_q, D)
    k_ref,  # (1, block_k, D)
    v_ref,  # (1, block_k, Dv)
    o_ref,  # (1, block_q, Dv)
    lse_ref,  # (1, block_q, _LANES) f32, lane-broadcast
    m_scr,  # (block_q, _LANES) f32
    l_scr,  # (block_q, _LANES) f32
    acc_scr,  # (block_q, Dv) f32
    *,
    blocks: _Blocks,
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

    def _compute(masked: bool):
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        if masked:
            mask = blocks.mask(qi, kj)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0:1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked rows: keep exp argument finite. Unmasked, every
        # row keeps a score, so m_new is finite and exp(NEG_INF - m_new) is
        # already 0: the guards would be identities.
        m_safe = (jnp.where(m_new <= NEG_INF / 2, 0.0, m_new) if masked
                  else m_new)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        if masked:
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)  # (bk, Dv)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    _run_blocks(blocks, qi, kj, _compute)

    @pl.when(kj == blocks.num_kv_blocks - 1)
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
    v: jax.Array,  # (B, T, Hkv, Dv)
    *,
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B, S, Hq, Dv), lse (B, Hq, S) fp32)."""
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    Dv = v.shape[-1]
    assert Hq % Hkv == 0
    blocks = _Blocks.of(S, T, block_q, block_k, causal, sliding_window)
    block_q, block_k = blocks.block_q, blocks.block_k

    # Pad sequence dims to block multiples (mask handles the tail).
    S_pad = blocks.num_q_blocks * block_q
    T_pad = blocks.num_kv_blocks * block_k
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    if T_pad != T:
        k = jnp.pad(k, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))

    # Head-major layout: (B*H, S, D).
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, Dv)

    grid = (B * Hq, blocks.num_q_blocks, blocks.num_kv_blocks)

    def q_index(bh, qi, kj):
        return (bh, qi, 0)

    kv_index = _kv_index_map(blocks, Hq, Hkv)

    kernel = functools.partial(
        _fwd_kernel,
        blocks=blocks,
        logit_softcap=logit_softcap,
        scale=scale,
    )

    fwd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, Dv), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), q_index),
            pl.BlockSpec((1, block_q, _LANES), q_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, S_pad, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, S_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )
    with jax.named_scope("flash_fwd"):
        out, lse = fwd(qh, kh, vh)

    out = out.reshape(B, Hq, S_pad, Dv).transpose(0, 2, 1, 3)[:, :S]
    lse = lse[..., 0].reshape(B, Hq, S_pad)[:, :, :S]
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (recompute p from q, k, lse — FlashAttention-2 scheme)
# ---------------------------------------------------------------------------


def _recompute_p_ds(q, k, lse, do, v, delta, mask, *, logit_softcap, scale):
    """Shared recompute: returns (p, ds_raw), both (bq, bk) fp32.

    ``lse``/``delta`` are (bq, 1). Invalid rows carry lse = NEG_INF.
    ``mask`` is None on a full pair: every row keeps a score there, so its
    lse is finite.
    """
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    if mask is None:
        p = jnp.exp(s - lse)
    else:
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
    blocks: _Blocks,
    logit_softcap: Optional[float],
    scale: float,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(masked: bool):
        mask = blocks.mask(qi, kj) if masked else None
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

    _run_blocks(blocks, qi, kj, _compute)

    @pl.when(kj == blocks.num_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,  # (block_k, D), (block_k, Dv) f32
    *,
    blocks: _Blocks,
    group_steps: int,  # G * num_q_blocks
    logit_softcap: Optional[float],
    scale: float,
):
    kj = pl.program_id(1)
    t = pl.program_id(2)  # g * num_q_blocks + qi
    qi = t % blocks.num_q_blocks

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute(masked: bool):
        mask = blocks.mask(qi, kj) if masked else None
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

    _run_blocks(blocks, qi, kj, _compute)

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
    Dv = v.shape[-1]
    G = Hq // Hkv
    blocks = _Blocks.of(S, T, block_q, block_k, causal, sliding_window)
    block_q, block_k = blocks.block_q, blocks.block_k
    num_q_blocks, num_kv_blocks = blocks.num_q_blocks, blocks.num_kv_blocks
    S_pad = num_q_blocks * block_q
    T_pad = num_kv_blocks * block_k

    def pad_s(x):
        return jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0), (0, 0))) \
            if S_pad != S else x

    def pad_t(x):
        return jnp.pad(x, ((0, 0), (0, T_pad - T), (0, 0), (0, 0))) \
            if T_pad != T else x

    qh = pad_s(q).transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    doh = pad_s(do).transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, Dv)
    oh = pad_s(out).transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, Dv)
    kh = pad_t(k).transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, D)
    vh = pad_t(v).transpose(0, 2, 1, 3).reshape(B * Hkv, T_pad, Dv)

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

    def q_index(bh, qi, kj):
        return (bh, qi, 0)

    kv_index = _kv_index_map(blocks, Hq, Hkv)

    common = dict(blocks=blocks, logit_softcap=logit_softcap, scale=scale)

    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B * Hq, num_q_blocks, num_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, Dv), kv_index),
            pl.BlockSpec((1, block_q, Dv), q_index),
            pl.BlockSpec((1, block_q, _LANES), q_index),
            pl.BlockSpec((1, block_q, _LANES), q_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_index),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S_pad, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_dq",
    )
    with jax.named_scope("flash_dq"):
        dq = dq_call(qh, kh, vh, doh, lse_h, delta)

    # dKV pass: one sequential sweep over every (group head, q block) pair
    # per KV block, accumulating in VMEM scratch.
    def kv_self_index(bhkv, kj, t):
        return (bhkv, kj, 0)

    q_group_index = _q_group_index_map(blocks, Hq, Hkv)

    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, group_steps=G * num_q_blocks, **common),
        grid=(B * Hkv, num_kv_blocks, G * num_q_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_group_index),
            pl.BlockSpec((1, block_k, D), kv_self_index),
            pl.BlockSpec((1, block_k, Dv), kv_self_index),
            pl.BlockSpec((1, block_q, Dv), q_group_index),
            pl.BlockSpec((1, block_q, _LANES), q_group_index),
            pl.BlockSpec((1, block_q, _LANES), q_group_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), kv_self_index),
            pl.BlockSpec((1, block_k, Dv), kv_self_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, T_pad, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, T_pad, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_dkv",
    )
    with jax.named_scope("flash_dkv"):
        dk, dv = dkv_call(qh, kh, vh, doh, lse_h, delta)

    dq = dq.reshape(B, Hq, S_pad, D).transpose(0, 2, 1, 3)[:, :S]
    dk = dk.reshape(B, Hkv, T_pad, D).transpose(0, 2, 1, 3)[:, :T]
    dv = dv.reshape(B, Hkv, T_pad, Dv).transpose(0, 2, 1, 3)[:, :T]
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
    v: jax.Array,  # (B, T, Hkv, Dv)
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
