"""Pure-jnp reference oracles for every kernel in this package.

These are the ground truth for kernel allclose tests AND the portable
fallback implementations the layer library dispatches to on backends without
the Pallas kernels (paper §4.2: per-backend kernel dispatch is a config
choice).

Conventions:
  q: (B, S, Hq, D), k/v: (B, T, Hkv, D) with Hq % Hkv == 0 (GQA).
  Masks are built from absolute positions so the same code serves full
  forward, prefill, and single-token decode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "attention_mask",
    "reference_attention",
    "blockwise_attention",
    "reference_rmsnorm",
    "reference_wkv6",
    "reference_wkv6_recurrent",
]

NEG_INF = -1e30


def attention_mask(
    q_positions: jax.Array,
    k_positions: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Boolean (..., S, T) mask; True = attend.

    ``q_positions``/``k_positions`` are absolute token positions (any
    broadcastable leading dims). Invalid cache slots should carry position
    -1 (masked by causality for q_pos >= 0 ... but also k_pos >= 0 check).
    """
    q = q_positions[..., :, None]
    k = k_positions[..., None, :]
    mask = k >= 0
    if causal:
        mask = mask & (k <= q)
    if sliding_window is not None:
        mask = mask & (k > q - sliding_window)
    return mask


def _norm_positions(p: jax.Array) -> jax.Array:
    """Normalizes positions to (B, S) (B=1 broadcast for shared positions)."""
    p = jnp.asarray(p)
    return p[None, :] if p.ndim == 1 else p


def _soft_cap(logits: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    logits_shard_fn=None,
) -> jax.Array:
    """Full-materialization softmax attention (the oracle).

    ``logits_shard_fn`` (optional) constrains the (B,Hkv,G,S,T) logits
    sharding — used by decode with sequence-sharded KV caches so GSPMD keeps
    the flash-decoding layout (partial softmax + small all-reduces) instead
    of gathering the cache."""
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    q_positions = _norm_positions(q_positions if q_positions is not None else jnp.arange(S))
    k_positions = _norm_positions(k_positions if k_positions is not None else jnp.arange(T))
    scale = (D ** -0.5) if scale is None else scale

    # Native-dtype inputs, fp32 accumulation (MXU semantics; identical for
    # fp32 inputs, and no duplicated fp32 copies of bf16 KV caches).
    qg = q.reshape(B, S, Hkv, G, D) * scale
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    logits = _soft_cap(logits, logit_softcap)
    mask = attention_mask(q_positions, k_positions, causal=causal,
                          sliding_window=sliding_window)  # (b|1, S, T)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    if logits_shard_fn is not None:
        logits = logits_shard_fn(logits)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, S, Hq, v.shape[-1])
    return out.astype(q.dtype)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    chunk_size: int = 512,
) -> jax.Array:
    """Query-chunked attention: O(chunk * T) live memory, pure XLA.

    This is the portable production path; mathematically identical to
    :func:`reference_attention`.
    """
    B, S, Hq, D = q.shape
    _, T, Hkv, _ = k.shape
    G = Hq // Hkv
    q_positions = _norm_positions(q_positions if q_positions is not None else jnp.arange(S))
    k_positions = _norm_positions(k_positions if k_positions is not None else jnp.arange(T))
    scale = (D ** -0.5) if scale is None else scale

    if S % chunk_size != 0:
        # Fall back for ragged sizes (decode steps, tests).
        return reference_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, sliding_window=sliding_window,
            logit_softcap=logit_softcap, scale=scale)

    n_chunks = S // chunk_size
    qc = q.reshape(B, n_chunks, chunk_size, Hkv, G, D)
    Bp = q_positions.shape[0]
    qp = q_positions.reshape(Bp, n_chunks, chunk_size)

    def one_chunk(args):
        # Inputs stay in their native dtype (bf16 in production); matmuls
        # accumulate in fp32 via preferred_element_type — TPU MXU semantics,
        # and half the HBM traffic of explicit fp32 upcasts.
        q_blk, qp_blk = args  # (B,c,Hkv,G,D), (Bp,c)
        logits = jnp.einsum("bskgd,btkd->bkgst", q_blk * scale, k,
                            preferred_element_type=jnp.float32)
        logits = _soft_cap(logits, logit_softcap)
        mask = attention_mask(qp_blk, k_positions, causal=causal,
                              sliding_window=sliding_window)  # (b|1, c, T)
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    # Scanned so one chunk's logits are live at a time.
    _, out = jax.lax.scan(lambda c, xs: (c, one_chunk(xs)), 0,
                          (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(qp, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, S, Hq, v.shape[-1])
    return out


def reference_rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


# ----------------------------- RWKV6 (WKV) ----------------------------------


def reference_wkv6_recurrent(
    r: jax.Array,  # (B, T, H, K)
    k: jax.Array,  # (B, T, H, K)
    v: jax.Array,  # (B, T, H, V)
    w: jax.Array,  # (B, T, H, K)  per-step decay in (0, 1), data-dependent
    u: jax.Array,  # (H, K)        bonus for current token
    state: Optional[jax.Array] = None,  # (B, H, K, V)
):
    """Naive stepwise WKV6 recurrence (the oracle).

    s_t = diag(w_t) s_{t-1} + k_t v_t^T ;  o_t = r_t (s_{t-1} + diag(u) k_t v_t^T)
    Returns (out (B,T,H,V), final_state).
    """
    B, T, H, K = r.shape
    V = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(s, inputs):
        r_t, k_t, v_t, w_t = inputs  # (B,H,K),(B,H,K),(B,H,V),(B,H,K)
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        o_t = jnp.einsum("bhk,bhkv->bhv", r_t, s + uf[None, :, :, None] * kv)
        s = w_t[..., None] * s + kv
        return s, o_t

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (rf, kf, vf, wf))
    final, out = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1).astype(r.dtype), final


def reference_wkv6(
    r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array, u: jax.Array,
    state: Optional[jax.Array] = None, *, chunk_size: int = 64,
):
    """Chunked (parallel-within-chunk) WKV6 — same math as the recurrence.

    Within a chunk of length C, with cumulative decays
    A_i = prod_{j<=i} w_j (exclusive of the state step ordering):
      contribution of state:  o_i += r_i diag(prod_{j<i} w_j) s_in
      intra-chunk:            o_i += sum_{j<i} r_i diag(prod_{j in (j, i)} w) k_j v_j^T
                                      + r_i diag(u) k_i v_i^T
      state update:           s_out = diag(prod_j w_j) s_in + sum_j diag(prod_{l>j} w_l) k_j v_j^T
    """
    B, T, H, K = r.shape
    V = v.shape[-1]
    C = chunk_size
    if T % C != 0:
        return reference_wkv6_recurrent(r, k, v, w, u, state)
    if state is None:
        state = jnp.zeros((B, H, K, V), jnp.float32)
    n = T // C
    rf, kf, vf, wf = (jnp.moveaxis(a.astype(jnp.float32).reshape(B, n, C, H, -1), 1, 0)
                      for a in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def chunk_step(s, inputs):
        r_c, k_c, v_c, w_c = inputs  # (B, C, H, *)
        logw = jnp.log(jnp.maximum(w_c, 1e-20))  # (B,C,H,K)
        cum = jnp.cumsum(logw, axis=1)  # inclusive prod_{j<=i}
        cum_excl = cum - logw  # exclusive prod_{j<i}
        total = cum[:, -1]  # (B,H,K)
        # state contribution: r_i * prod_{j<i} w_j  @ s
        r_dec = r_c * jnp.exp(cum_excl)
        o = jnp.einsum("bihk,bhkv->bihv", r_dec, s)
        # intra-chunk: pair (i, j<i): decay prod_{j<l<i} w_l = exp(cum_excl_i - cum_j)
        # Factorized intra-chunk decay exp(cum_excl_i - cum_j). The combined
        # exponent is <= 0 for j < i, but the split factors can overflow, so
        # re-center on the mid-chunk cumulative decay.
        mid = cum[:, C // 2][:, None]  # (B,1,H,K)
        ri = r_c * jnp.exp(cum_excl - mid)  # (B,i,H,K)
        kj = k_c * jnp.exp(mid - cum)  # (B,j,H,K)
        att = jnp.einsum("bihk,bjhk->bijh", ri, kj)
        mask = jnp.tril(jnp.ones((C, C), bool), k=-1)[None, :, :, None]
        att = jnp.where(mask, att, 0.0)
        o = o + jnp.einsum("bijh,bjhv->bihv", att, v_c)
        # current-token bonus
        bonus = jnp.einsum("bihk,bihk->bih", r_c * uf[None, None], k_c)
        o = o + bonus[..., None] * v_c
        # state update
        k_dec = k_c * jnp.exp(total[:, None] - cum)
        s = jnp.exp(total)[..., None] * s + jnp.einsum("bjhk,bjhv->bhkv", k_dec, v_c)
        return s, o

    final, out = jax.lax.scan(chunk_step, state, (rf, kf, vf, wf))
    out = jnp.moveaxis(out, 0, 1).reshape(B, T, H, V)
    return out.astype(r.dtype), final
