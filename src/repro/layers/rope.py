"""Rotary position embeddings as a *swappable child module*.

The paper's flagship modularity example: RoPE variants integrate into any
model via config replacement, never by editing attention code. The attention
layer only knows the interface ``apply(x, positions) -> x`` — theta, scaling
strategy, partial-rotary etc. are encapsulated here.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, Required, config_class
from repro.layers.base import BaseLayer

__all__ = ["BaseRotaryEmbedding", "RotaryEmbedding", "PairRotaryEmbedding",
           "LinearScaledRotaryEmbedding"]


class BaseRotaryEmbedding(BaseLayer):
    """Interface: apply(x, positions) with x (B, S, H, D), positions (S,)."""

    @config_class
    class Config(BaseLayer.Config):
        dim: Required[int] = REQUIRED  # rotary dim (== head_dim typically)

    def apply(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        raise NotImplementedError


def _rope_sin_cos(positions: jax.Array, dim: int, theta: float) -> tuple:
    # freqs: theta^(-2i/dim), i in [0, dim/2). positions: (S,) or (B, S).
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., S, dim/2)
    return jnp.sin(angles), jnp.cos(angles)


def _apply_half_rotation(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """GPT-NeoX / Llama convention: rotate (x[:d/2], x[d/2:])."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:  # (S, d/2) shared across batch
        sin = sin[None, :, None, :]
        cos = cos[None, :, None, :]
    else:  # (B, S, d/2) per-row positions (continuous batching decode)
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


class RotaryEmbedding(BaseRotaryEmbedding):
    """Standard RoPE (Su et al.)."""

    @config_class
    class Config(BaseRotaryEmbedding.Config):
        theta: float = 10000.0
        # Fraction of head_dim that is rotated (1.0 = full rotary).
        rotary_pct: float = 1.0

    def apply(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        cfg = self.config
        rot_dim = int(cfg.dim * cfg.rotary_pct)
        rot_dim -= rot_dim % 2
        sin, cos = _rope_sin_cos(positions, rot_dim, cfg.theta)
        if rot_dim == x.shape[-1]:
            return _apply_half_rotation(x, sin, cos)
        x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
        return jnp.concatenate([_apply_half_rotation(x_rot, sin, cos), x_pass], axis=-1)


class PairRotaryEmbedding(RotaryEmbedding):
    """DeepSeek's layout: rotates the adjacent pairs (x[2i], x[2i+1]) by
    position * theta^(-2i/dim) and returns them de-interleaved, rotated
    first halves then second halves, as DeepSeek-V3's
    ``apply_rotary_pos_emb`` does (it views the last dim as (dim/2, 2) and
    transposes before the half rotation)."""

    def apply(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        return super().apply(x, positions)


class LinearScaledRotaryEmbedding(RotaryEmbedding):
    """Position-interpolation RoPE variant — exists to demonstrate the O(1)
    integration claim (swap via replace_config; attention code untouched)."""

    @config_class
    class Config(RotaryEmbedding.Config):
        scaling_factor: float = 1.0

    def apply(self, x: jax.Array, positions: jax.Array) -> jax.Array:
        scaled = positions.astype(jnp.float32) / self.config.scaling_factor
        # Re-entrant same-module call: runs in the current context frame.
        return super().apply(x, scaled)
