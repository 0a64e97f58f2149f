"""Thin dispatchers over the kernel registry.

The layer library calls these; backend selection (real TPU kernel vs
interpret-mode validation vs pure-XLA fallback) is resolved per call site by
``repro.kernels.registry`` from one :class:`KernelConfig` — a *config*
choice threaded from mesh rules (paper §4.2), never a code change.

Each dispatcher only (a) derives the call's :class:`KernelFeatures` from its
arguments (the old ``_same_positions`` / 1-token / paged fallback branches
are now capability predicates in the registry) and (b) invokes the resolved
spec. Resolution is memoized, so the hot-path overhead is one dict lookup.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.registry import (DEFAULT_CONFIG, KernelConfig,
                                    KernelFeatures)

__all__ = ["flash_attention", "decode_attention", "paged_gather_kv",
           "rmsnorm", "wkv6", "wkv6_decode", "grouped_matmul"]


def _same_positions(q_positions, k_positions) -> bool:
    """True iff q/k positions are provably identical (so the contiguous
    self-attention kernel applies).

    Checks by *value* for concrete arrays — callers frequently pass
    equal-but-distinct position arrays (e.g. two ``jnp.arange(S)`` calls),
    which an identity-only check would silently send down the
    O(S*T)-materializing reference path. Traced (abstract) values can't be
    value-compared, so they fall back to the identity check.
    """
    if q_positions is None and k_positions is None:
        return True
    if q_positions is k_positions:
        return True
    if q_positions is None or k_positions is None:
        return False
    q_shape = getattr(q_positions, "shape", None)
    if q_shape != getattr(k_positions, "shape", None):
        return False
    try:
        import numpy as np

        if isinstance(q_positions, jax.core.Tracer) or \
                isinstance(k_positions, jax.core.Tracer):
            return False
        return bool(np.array_equal(np.asarray(q_positions),
                                   np.asarray(k_positions)))
    except (TypeError, jax.errors.ConcretizationTypeError):
        return False


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions=None,
    k_positions=None,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    kernel: Optional[KernelConfig] = None,
    needs_grad: bool = False,
    partition=None,
) -> jax.Array:
    """Full-sequence attention (``attention.fwd``).

    The Pallas kernel carries a recompute-based custom_vjp, so it is legal
    under ``jax.grad`` and serves as the training kernel. Ragged-position or
    1-token calls resolve to blockwise/ref via capability predicates.

    ``partition`` names the mesh axes of q/k/v's (B, S, H, D) dims. A
    backend GSPMD cannot partition runs per shard under it on a
    multi-device mesh; each shard needs whole sequences.
    """
    kernel = kernel if kernel is not None else DEFAULT_CONFIG
    feats = KernelFeatures(
        platform=registry.current_platform(),
        dtype=str(q.dtype),
        needs_grad=needs_grad,
        ragged_positions=not _same_positions(q_positions, k_positions),
        single_query=q.shape[1] == 1,
        sliding_window=sliding_window is not None,
    )
    spec = registry.resolve_backend("attention.fwd", feats, kernel)
    call = functools.partial(
        spec.fn, q_positions=q_positions, k_positions=k_positions,
        causal=causal, sliding_window=sliding_window,
        logit_softcap=logit_softcap, scale=scale,
        cfg=kernel)
    if not spec.per_shard:
        return call(q, k, v)
    return registry.per_shard(call, partition, q, k, v, whole_dims=(1,))


def paged_gather_kv(k_pool: jax.Array, v_pool: jax.Array,
                    pos_pool: jax.Array, page_tables: jax.Array,
                    scale_pool: Optional[jax.Array] = None):
    """Materialize a head-major ``(P, Hkv, page, D)`` paged pool into
    contiguous per-sequence ``(B, N*page, Hkv, D)`` K/V + positions via an
    XLA gather — the portable reference path for paged decode. Unmapped
    logical pages (table entry -1) gather physical page 0 but their
    positions are forced to -1, so masking drops them.

    ``scale_pool`` (quantized pools: (P, page, 2) per-slot fp32 scales) is
    gathered through the same table and applied, so callers always receive
    dequantized fp32 K/V — the storage format stays opaque here.
    """
    tbl = jnp.asarray(page_tables, jnp.int32)  # (B, N)
    B, N = tbl.shape
    P, Hkv, page, D = k_pool.shape
    safe = jnp.maximum(tbl, 0)
    k = jnp.swapaxes(k_pool[safe], 2, 3).reshape(B, N * page, Hkv, D)
    v = jnp.swapaxes(v_pool[safe], 2, 3).reshape(B, N * page, Hkv, D)
    if scale_pool is not None:
        from repro.quantization import kv as kv_quant

        scales = scale_pool[safe].reshape(B, N * page, 2)
        k, v = kv_quant.dequantize_kv(k, v, scales)
    kpos = jnp.where((tbl >= 0)[:, :, None], pos_pool[safe], -1)
    return k, v, kpos.reshape(B, N * page)


def decode_attention(
    q: jax.Array,  # (B, S', Hq, D)
    k: jax.Array,  # (B, Hkv, T, D) cache — or (P, Hkv, page, D) pool (paged)
    v: jax.Array,
    *,
    q_positions,  # (B, S') or (S',) absolute positions of the new tokens
    k_positions,  # (B, T)/(T,) slot positions — or (P, page) pos pool (paged)
    page_tables: Optional[jax.Array] = None,  # (B, N) int32, -1 = unmapped
    scale_pool: Optional[jax.Array] = None,  # (P, page, 2) fp32 (quantized)
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    replicated_cache: bool = True,
    logits_shard_fn=None,
    kernel: Optional[KernelConfig] = None,
) -> jax.Array:
    """Decode-step attention over a (ring-buffer or paged) cache
    (``attention.decode``).

    The Pallas backend streams the cache through VMEM once per KV group and
    never materializes the ``(B, Hkv, G, S', T)`` logits tensor; with
    ``page_tables`` it DMAs exactly the pages named by each sequence's table
    row (scalar prefetch). The ref backend materializes logits (optionally
    constrained by ``logits_shard_fn`` for sequence-sharded caches) and
    gathers paged pools in XLA.

    ``replicated_cache=False`` declares a mesh-sharded KV cache; capability
    predicates then reject the Pallas backend (no shard_map plumbing yet).
    """
    # Decode positions are never inferable (queries continue an absolute
    # position stream; cache slots hold arbitrary ring positions) — a
    # guessed default would silently mask nearly everything.
    if q_positions is None or k_positions is None:
        raise ValueError("decode_attention requires explicit q_positions "
                         "and k_positions (cache pos tensor)")
    kernel = kernel if kernel is not None else DEFAULT_CONFIG
    feats = KernelFeatures(
        platform=registry.current_platform(),
        dtype=str(q.dtype),
        paged=page_tables is not None,
        sliding_window=sliding_window is not None,
        replicated_cache=replicated_cache,
        # Chunked-prefill / speculative verify windows (S' > 1) resolve
        # separately from 1-token decode steps: the query dim is a real
        # matmul dim there, so backends may tile it differently.
        multi_query=q.shape[1] > 1,
        # KV *storage* dtype as a capability: quantized pools (int8/fp8 +
        # scale_pool) resolve only to backends that dequantize in-kernel
        # (pallas) or gather-dequantize (ref).
        kv_dtype=str(k.dtype),
    )
    spec = registry.resolve_backend("attention.decode", feats, kernel)
    return spec.fn(
        q, k, v, q_positions=q_positions, k_positions=k_positions,
        page_tables=page_tables, scale_pool=scale_pool, causal=causal,
        sliding_window=sliding_window, logit_softcap=logit_softcap,
        scale=scale, logits_shard_fn=logits_shard_fn,
        cfg=kernel)


def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
            kernel: Optional[KernelConfig] = None,
            needs_grad: bool = False) -> jax.Array:
    """RMS normalization (``rmsnorm``). The Pallas kernel is forward-only;
    training resolves to the (autodiffable) ref path via predicates."""
    kernel = kernel if kernel is not None else DEFAULT_CONFIG
    feats = KernelFeatures(
        platform=registry.current_platform(),
        dtype=str(x.dtype),
        needs_grad=needs_grad,
    )
    spec = registry.resolve_backend("rmsnorm", feats, kernel)
    return spec.fn(x, scale, eps=eps,
                   cfg=kernel)


def wkv6_decode(r, k, v, w, u, state, *,
                kernel: Optional[KernelConfig] = None):
    """O(1) recurrent WKV6 step (``wkv6.decode``): one token against the
    carried (B, H, K, V) state."""
    kernel = kernel if kernel is not None else DEFAULT_CONFIG
    feats = KernelFeatures(platform=registry.current_platform(),
                           dtype=str(r.dtype))
    spec = registry.resolve_backend("wkv6.decode", feats, kernel)
    return spec.fn(r, k, v, w, u, state,
                   cfg=kernel)


def wkv6(r, k, v, w, u, state=None, *,
         kernel: Optional[KernelConfig] = None, needs_grad: bool = False):
    """WKV6 core (``wkv6``). Pallas chunked kernel where eligible
    (forward-only); chunked-jnp ref otherwise."""
    kernel = kernel if kernel is not None else DEFAULT_CONFIG
    feats = KernelFeatures(
        platform=registry.current_platform(),
        dtype=str(r.dtype),
        needs_grad=needs_grad,
    )
    spec = registry.resolve_backend("wkv6", feats, kernel)
    return spec.fn(r, k, v, w, u, state,
                   cfg=kernel)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   kernel: Optional[KernelConfig] = None) -> jax.Array:
    """Grouped matrix product (``moe.gmm``): rows ``lhs`` (m, k) in group
    order, one (k, n) matrix per group in ``rhs``, ``group_sizes`` (groups,)
    int32 summing to at most m. Rows past the groups are undefined."""
    kernel = kernel if kernel is not None else DEFAULT_CONFIG
    feats = KernelFeatures(platform=registry.current_platform(),
                           dtype=str(lhs.dtype))
    spec = registry.resolve_backend("moe.gmm", feats, kernel)
    return spec.fn(lhs, rhs, group_sizes, cfg=kernel)
