"""Quantized paged-KV storage formats: int8 / fp8-e4m3 pools + scale rows.

The paged KV cache stores each token slot's K and V rows in a
low-precision storage dtype with one fp32 scale per (slot, k|v) carried
in a ``scale_pool`` leaf of shape ``(num_pages, page_size, 2)`` that
lives alongside ``k_pool``/``v_pool`` in the cache tree.  Because the
scale row shares the physical-page axis with the payload pools, every
page operation the serving stack performs — COW forks, evict-to-host,
restore, prefix-page sharing — moves the scales atomically with the KV
bytes by construction, and a prefix hit replays *bitwise identical*
quantized pages (quantization is deterministic, so shared pages equal a
cold prefill's).

Resolution is declarative: a layer's ``kv_cache_dtype`` resolves through
:func:`pool_format` into either ``None`` (plain ``astype`` storage —
fp32/bf16, and fp8 on the *dense* layout which has nowhere to put
scales) or a :class:`KVQuantFormat` the attention layer and kernels
treat as opaque.  Per-slot scaling (amax over the slot's ``(heads,
head_dim)`` rows) keeps the round-trip error relative to each token's
own magnitude: ~0.4% worst-case for int8, ~6% for e4m3's 3-bit mantissa.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.quantization import numerics

__all__ = [
    "KVQuantFormat",
    "INT8_KV",
    "FP8_E4M3_KV",
    "format_by_name",
    "pool_format",
    "storage_dtype",
    "dtype_by_name",
    "init_scale_pool",
    "quantize_kv_write",
    "dequantize_kv",
]


@dataclasses.dataclass(frozen=True)
class KVQuantFormat:
    """One quantized pool storage scheme (opaque outside this package)."""

    name: str
    storage_dtype: Any
    qmax: float


INT8_KV = KVQuantFormat("int8", jnp.int8, numerics.INT8_QMAX)
FP8_E4M3_KV = KVQuantFormat("fp8_e4m3", jnp.float8_e4m3fn,
                            numerics.FP8_E4M3_MAX)

_BY_NAME = {f.name: f for f in (INT8_KV, FP8_E4M3_KV)}

# Serving-facing dtype names -> storage dtypes (the only place the string
# names resolve, so launch scripts never spell a low-precision dtype).
_DTYPE_NAMES = {
    "fp32": jnp.float32,
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
    "fp8_e4m3": jnp.float8_e4m3fn,
}


def format_by_name(name: str) -> KVQuantFormat:
    if name not in _BY_NAME:
        raise ValueError(f"unknown KV quant format {name!r}; "
                         f"known: {sorted(_BY_NAME)}")
    return _BY_NAME[name]


def dtype_by_name(name: str) -> Any:
    """A ``kv_cache_dtype`` value from a short serving-facing name."""
    if name not in _DTYPE_NAMES:
        raise ValueError(f"unknown kv dtype name {name!r}; "
                         f"known: {sorted(_DTYPE_NAMES)}")
    return _DTYPE_NAMES[name]


def pool_format(kv_cache_dtype: Any, *, layout: str
                ) -> Optional[KVQuantFormat]:
    """Resolve a layer's ``kv_cache_dtype`` into a pool quant format.

    * int8 -> :data:`INT8_KV`; requires the paged layout (the per-slot
      scales live in the page pool — a dense ring has nowhere to carry
      them), so a dense int8 config raises here, at layer build time.
    * float8_e4m3 on the paged layout -> :data:`FP8_E4M3_KV` (scaled
      storage); on the dense layout it keeps the historical plain
      ``astype`` cache (unscaled), preserving that path's semantics.
    * anything else -> ``None`` (plain ``astype`` storage).

    Accepts either a dtype or one of the short serving-facing names.
    """
    if isinstance(kv_cache_dtype, str) and kv_cache_dtype in _DTYPE_NAMES:
        kv_cache_dtype = _DTYPE_NAMES[kv_cache_dtype]
    dt = jnp.dtype(kv_cache_dtype)
    if dt == jnp.dtype(jnp.int8):
        if layout != "paged":
            raise ValueError(
                "int8 KV storage requires kv_cache_layout='paged': the "
                "per-slot scale rows live in the page pool (scale_pool)")
        return INT8_KV
    if dt == jnp.dtype(jnp.float8_e4m3fn) and layout == "paged":
        return FP8_E4M3_KV
    return None


def storage_dtype(kv_cache_dtype: Any, *, layout: str) -> Any:
    """The dtype the pool leaves are allocated in."""
    fmt = pool_format(kv_cache_dtype, layout=layout)
    return fmt.storage_dtype if fmt is not None else kv_cache_dtype


def init_scale_pool(num_pages: int, page_size: int) -> jax.Array:
    """Fresh scale rows: unit scales so uninitialized slots dequantize to
    their raw (zero) storage values."""
    return jnp.ones((num_pages, page_size, 2), jnp.float32)


def quantize_kv_write(k: jax.Array, v: jax.Array, fmt: KVQuantFormat
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Quantize a cache write per token slot.

    ``k``/``v`` are update rows shaped ``(..., heads, head_dim)`` with the
    token-slot axes leading; the scale for each slot is ``amax over its
    (heads, head_dim) rows / qmax``.  Returns storage-dtype ``(k_q, v_q)``
    plus fp32 ``scales`` shaped ``(..., 2)`` (k-scale, v-scale) ready to
    scatter into ``scale_pool``.
    """

    def one(x):
        amax = numerics.abs_amax(x, axis=(-2, -1))
        scale = jnp.maximum(amax, numerics._EPS) / fmt.qmax
        q = numerics.scaled_cast(x, scale[..., None, None],
                                 fmt.storage_dtype)
        return q, scale

    k_q, k_scale = one(k)
    v_q, v_scale = one(v)
    return k_q, v_q, jnp.stack([k_scale, v_scale], axis=-1)


def dequantize_kv(k: jax.Array, v: jax.Array, scales: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Inverse of :func:`quantize_kv_write` for gathered pool rows.

    ``k``/``v`` are ``(..., slots, heads, head_dim)`` storage values and
    ``scales`` is ``(..., slots, 2)``; returns fp32 dequantized rows.
    """
    k = numerics.dequantize(k, scales[..., 0][..., None, None])
    v = numerics.dequantize(v, scales[..., 1][..., None, None])
    return k, v
