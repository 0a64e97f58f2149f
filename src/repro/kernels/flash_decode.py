"""TPU Pallas flash-decode: split-KV online-softmax attention for decode.

The decode hot path (paper §6, Table 4 TPOT) is one-or-few query tokens
against a long KV cache. The reference path materializes the full
``(B, Hkv, G, S', T)`` logits tensor in f32 per token; this kernel never
does — it streams the cache in ``block_k`` chunks through VMEM, carrying
the online-softmax running max / denominator / accumulator in f32 VMEM
scratch (flash-decoding-style split-KV, with the split axis mapped to the
TPU grid's sequential innermost dimension).

Design points:
  * GQA-aware: the grid iterates (batch, kv_head, kv_block) and all G query
    heads of a group (x S' decode steps) are flattened into the rows of one
    q block — each KV block is fetched from HBM exactly once per group,
    not once per query head.
  * Masking comes directly from the cache's per-slot ``pos`` tensor
    (absolute positions, -1 = empty slot), so ring-buffer / sliding-window
    cache layouts need no gather or re-ordering: wrapped slots mask
    correctly wherever they physically live.
  * Fully-masked rows (e.g. empty continuous-batching slots) produce zeros
    (the reference path produces a degenerate uniform average instead; both
    are unused downstream, but zeros keep the kernel gather-free).
  * ``interpret=True`` runs the same kernel body under the Pallas
    interpreter for CPU validation (config choice, not code change: §4.2).

Cache layout is head-major — ``(B, Hkv, T, D)`` for the contiguous cache and
``(P, Hkv, page, D)`` for the paged pool — so each grid step's K/V block is a
``(rows, D)`` tile whose last two dims satisfy the TPU tiling rule for any
head count. Positions stay ``(B, T)`` / ``(P, page)``: the kernels fetch an
8-row slab of the position array (one sublane tile) and select their row in
VMEM, so neither the cache nor the positions is copied per decode step.

Forward only — decode is inference-only by construction. The kernel expects
a single-device or replicated KV cache: with sequence-sharded caches, use
the reference decode path (backend ``"ref"``), which constrains the
logits sharding so GSPMD keeps the flash-decoding layout; shard_map
plumbing for this kernel is future work.

Paged variant (:func:`paged_flash_decode_forward`): the KV cache is a shared
pool of fixed-size pages plus a per-sequence page table. The page table is a
*scalar-prefetch* operand (``pltpu.PrefetchScalarGridSpec``), so each grid
step DMAs exactly the physical page named by ``page_tables[b, j]`` — the
pool is never gathered or reordered in HBM. Unmapped logical pages
(table entry -1) are clamped to page 0 for the DMA and masked out entirely
in the kernel body (the mask reads the table, not the page contents, so no
"null page" content invariant is required for reads).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode_forward", "paged_flash_decode_forward"]

NEG_INF = -1e30
_LANES = 128  # VREG lane count: scratch second-minor dim
_SUBLANES = 8  # rows of one 32-bit VREG tile: position slabs are this tall


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_block_update(q, k, v, mask, m_scr, l_scr, acc_scr, *,
                         logit_softcap: Optional[float]):
    """One KV block's online-softmax update against q rows (shared by the
    contiguous and paged kernels)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, 0:1]  # (R, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # Guard fully-masked rows: keep the exp argument finite.
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_safe)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

    l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _finalize_output(o_ref, l_scr, acc_scr):
    l = l_scr[:, 0:1]
    denom = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _decode_mask(q_pos, k_pos, *, causal, sliding_window):
    # Empty slots (pos < 0) and padding rows are masked; ring wraparound is
    # handled for free because masking reads the slot's absolute position.
    mask = jnp.logical_and(k_pos >= 0, q_pos >= 0)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if sliding_window is not None:
        mask = jnp.logical_and(mask, k_pos > q_pos - sliding_window)
    return mask


def _kernel(
    q_ref,  # (1, 1, R, D): rows = S' decode steps x G grouped query heads
    k_ref,  # (1, 1, block_k, D)
    v_ref,  # (1, 1, block_k, D)
    qpos_ref,  # (1, R, 1) int32, -1 = padding row
    kpos_ref,  # (slab, block_k) int32 rows of batch b // slab, -1 = empty
    o_ref,  # (1, 1, R, D)
    m_scr,  # (R, _LANES) f32
    l_scr,  # (R, _LANES) f32
    acc_scr,  # (R, D) f32
    *,
    num_kv_blocks: int,
    kv_len: Optional[int],
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
):
    b = pl.program_id(0)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (R, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, D)
    k_pos = kpos_ref[pl.ds(b % kpos_ref.shape[0], 1), :]  # (1, bk)
    mask = _decode_mask(qpos_ref[0], k_pos, causal=causal,
                        sliding_window=sliding_window)
    if kv_len is not None:
        # Last block runs past the cache: its tail holds whatever the DMA
        # brought in, positions included. Mask those columns, and zero
        # their V rows so that 0 * garbage cannot reach the accumulator.
        bk = k.shape[0]
        col = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = jnp.logical_and(mask, col < kv_len)
        row = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where(row < kv_len, v, 0.0)
    _online_block_update(q, k, v, mask, m_scr, l_scr, acc_scr,
                         logit_softcap=logit_softcap)

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        _finalize_output(o_ref, l_scr, acc_scr)


def _paged_kernel(
    tbl_ref,  # (B, N) int32 scalar-prefetch page table, -1 = unmapped
    q_ref,  # (1, 1, R, D)
    k_ref,  # (1, 1, page, D): head h of the physical page named by tbl[b, j]
    v_ref,  # (1, 1, page, D)
    qpos_ref,  # (1, R, 1) int32, -1 = padding row
    kpos_ref,  # (slab, page) int32 positions of pages phys // slab, -1 = empty
    *rest,  # [scale_ref (1, page, 2) f32 when has_scales,] o_ref, scratches
    num_logical_pages: int,
    causal: bool,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    scale: float,
    has_scales: bool,
):
    if has_scales:
        scale_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        scale_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)  # (page, D)
    v = v_ref[0, 0].astype(jnp.float32)
    if scale_ref is not None:
        # Quantized pool: per-token-slot (k, v) scales ride in as a page-shaped
        # operand through the same scalar-prefetch table, so dequantization is
        # in-VMEM and the HBM pool stays in its storage dtype.
        sc = scale_ref[0].astype(jnp.float32)  # (page, 2)
        k = k * sc[:, 0:1]
        v = v * sc[:, 1:2]
    phys = jnp.maximum(tbl_ref[b, j], 0)
    k_pos = kpos_ref[pl.ds(phys % kpos_ref.shape[0], 1), :]  # (1, page)
    mask = _decode_mask(qpos_ref[0], k_pos, causal=causal,
                        sliding_window=sliding_window)
    # Unmapped logical pages were clamped to physical page 0 for the DMA;
    # masking on the TABLE entry (not the page contents) drops them exactly.
    mask = jnp.logical_and(mask, tbl_ref[b, j] >= 0)
    _online_block_update(q, k, v, mask, m_scr, l_scr, acc_scr,
                         logit_softcap=logit_softcap)

    @pl.when(j == num_logical_pages - 1)
    def _finalize():
        _finalize_output(o_ref, l_scr, acc_scr)


def _pack_q_rows(q: jax.Array, q_positions: jax.Array, Hkv: int):
    """(B, S', Hq, D) -> (B, Hkv, R_pad, D) rows of (s', g) pairs per KV
    group, plus the per-row positions as a (B, R_pad, 1) column (-1 =
    padding row). Rows pad to a whole tile of the query's dtype."""
    B, Sq, Hq, D = q.shape
    G = Hq // Hkv
    R = Sq * G
    row_tile = _SUBLANES * max(1, 4 // jnp.dtype(q.dtype).itemsize)
    R_pad = _round_up(R, row_tile)
    # q: (B, S', Hkv*G, D) -> (B, Hkv, S'*G, D); head h = kv * G + g.
    qr = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(B, Hkv, R, D)
    qpos_rows = jnp.repeat(q_positions, G, axis=1)  # (B, R): row r -> q_pos[r // G]
    if R_pad != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, R_pad - R), (0, 0)))
        qpos_rows = jnp.pad(qpos_rows, ((0, 0), (0, R_pad - R)),
                            constant_values=-1)
    return qr, qpos_rows[:, :, None], R, R_pad


def _pos_slab(n: int) -> int:
    """Rows of a position block: one sublane tile, or the whole (short)
    array. A trailing partial slab is fine — the selected row is in bounds."""
    return min(n, _SUBLANES)


def flash_decode_forward(
    q: jax.Array,  # (B, S', Hq, D), S' small (decode steps)
    k: jax.Array,  # (B, Hkv, T, D) — the cache, any slot order
    v: jax.Array,
    q_positions: jax.Array,  # (B, S') absolute positions of the new tokens
    k_positions: jax.Array,  # (B, T) per-slot absolute positions, -1 = empty
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale

    q_positions = jnp.broadcast_to(jnp.asarray(q_positions, jnp.int32), (B, Sq))
    k_positions = jnp.broadcast_to(jnp.asarray(k_positions, jnp.int32), (B, T))

    qr, qpos_rows, R, R_pad = _pack_q_rows(q, q_positions, Hkv)

    # The whole cache in one tile when it fits; else lane-aligned tiles, the
    # last one running past the cache (masked in the kernel — the cache is
    # never padded, which would copy it every step).
    if T <= block_k:
        block_k = T
    elif block_k % _LANES:
        raise ValueError(f"decode block_k {block_k} must be a multiple of "
                         f"{_LANES} for a cache of {T} slots")
    num_kv_blocks = -(-T // block_k)
    slab = _pos_slab(B)

    grid = (B, Hkv, num_kv_blocks)
    kernel = functools.partial(
        _kernel,
        num_kv_blocks=num_kv_blocks,
        kv_len=T if T % block_k else None,
        causal=causal,
        sliding_window=sliding_window,
        logit_softcap=logit_softcap,
        scale=scale,
    )

    decode = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, R_pad, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, R_pad, 1), lambda b, h, j: (b, 0, 0)),
            pl.BlockSpec((slab, block_k), lambda b, h, j: (b // slab, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, R_pad, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((R_pad, _LANES), jnp.float32),
            pltpu.VMEM((R_pad, _LANES), jnp.float32),
            pltpu.VMEM((R_pad, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode",
    )
    with jax.named_scope("paged_decode"):
        out = decode(qr, k, v, qpos_rows, k_positions)

    # (B, Hkv, R, D) -> (B, S', Hq, D).
    out = out[:, :, :R].reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sq, Hq, D)


def paged_flash_decode_forward(
    q: jax.Array,  # (B, S', Hq, D), S' small (decode steps)
    k_pool: jax.Array,  # (P, Hkv, page, D) — shared physical page pool
    v_pool: jax.Array,  # (P, Hkv, page, D)
    pos_pool: jax.Array,  # (P, page) int32 per-token positions, -1 = empty
    page_tables: jax.Array,  # (B, N) int32 physical page ids, -1 = unmapped
    q_positions: jax.Array,  # (B, S') absolute positions of the new tokens
    *,
    scale_pool: Optional[jax.Array] = None,  # (P, page, 2) f32 dequant scales
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over a paged KV cache via scalar-prefetch page tables.

    The grid is (B, Hkv, N logical pages); for grid step (b, h, j) the
    BlockSpec index map reads ``page_tables[b, j]`` (a prefetched scalar) and
    DMAs that physical page — one page fetch per KV group, no HBM gather.
    Unmapped entries clamp to page 0 and are masked via the table entry.

    The K/V block of a grid step is one head of one page, ``(page, D)``:
    the pool's own trailing dims, so every page size tiles.
    """
    B, Sq, Hq, D = q.shape
    P, Hkv, page, _ = k_pool.shape
    _, N = page_tables.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale

    q_positions = jnp.broadcast_to(jnp.asarray(q_positions, jnp.int32), (B, Sq))
    page_tables = jnp.asarray(page_tables, jnp.int32)
    pos_pool = jnp.asarray(pos_pool, jnp.int32)

    qr, qpos_rows, R, R_pad = _pack_q_rows(q, q_positions, Hkv)

    has_scales = scale_pool is not None
    kernel = functools.partial(
        _paged_kernel,
        num_logical_pages=N,
        causal=causal,
        sliding_window=sliding_window,
        logit_softcap=logit_softcap,
        scale=scale,
        has_scales=has_scales,
    )

    def phys(b, j, tbl):
        return jnp.maximum(tbl[b, j], 0)

    slab = _pos_slab(P)
    in_specs = [
        pl.BlockSpec((1, 1, R_pad, D), lambda b, h, j, tbl: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda b, h, j, tbl: (phys(b, j, tbl), h, 0, 0)),
        pl.BlockSpec((1, 1, page, D),
                     lambda b, h, j, tbl: (phys(b, j, tbl), h, 0, 0)),
        pl.BlockSpec((1, R_pad, 1), lambda b, h, j, tbl: (b, 0, 0)),
        pl.BlockSpec((slab, page),
                     lambda b, h, j, tbl: (phys(b, j, tbl) // slab, 0)),
    ]
    operands = [page_tables, qr, k_pool, v_pool, qpos_rows, pos_pool]
    if has_scales:
        # Dequant scales follow the same table-indexed page fetch as K/V.
        in_specs.append(pl.BlockSpec(
            (1, page, 2), lambda b, h, j, tbl: (phys(b, j, tbl), 0, 0)))
        operands.append(jnp.asarray(scale_pool, jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, N),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, R_pad, D), lambda b, h, j, tbl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((R_pad, _LANES), jnp.float32),
            pltpu.VMEM((R_pad, _LANES), jnp.float32),
            pltpu.VMEM((R_pad, D), jnp.float32),
        ],
    )

    decode = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R_pad, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode",
    )
    with jax.named_scope("paged_decode"):
        out = decode(*operands)

    out = out[:, :, :R].reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sq, Hq, D)
