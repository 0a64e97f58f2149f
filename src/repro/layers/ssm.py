"""Mamba-1 selective SSM token mixer (for the Jamba hybrid stack).

TPU adaptation: the recurrence h_t = Ā_t h_{t-1} + B̄_t x_t is evaluated with
``jax.lax.associative_scan`` (log-depth parallel prefix) instead of a CUDA
selective-scan kernel — the TPU-idiomatic mapping of the paper's
"hand-tuned kernels where compilers fall short" principle. Decode keeps an
O(1) state: (h, conv ring), which is why jamba runs the 524k-token shape.

Implements the token-mixer interface (drop-in for attention in
TransformerLayer — the hybrid stack is pure config).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, Required, config_class
from repro.core.module import no_context
from repro.core.utils import PartitionSpecLike, remat_name
from repro.layers.base import BaseLayer, ParameterSpec, fan_in_init, normal_init, zeros_init

__all__ = ["MambaMixer"]


def _a_log_init():
    def init(key, shape, dtype):
        # S4D-real init: A = -(1..N) per channel.
        d_inner, n = shape
        a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (d_inner, 1))
        return jnp.log(a).astype(dtype)

    return init


def _dt_bias_init(dt_min=1e-3, dt_max=1e-1):
    def init(key, shape, dtype):
        # Sample dt uniformly in log space; store softplus^-1(dt).
        u = jax.random.uniform(key, shape)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
        return jnp.log(jnp.expm1(dt)).astype(dtype)

    return init


class MambaMixer(BaseLayer):
    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        state_dim: int = 16
        conv_width: int = 4
        expand: int = 2
        dt_rank: Optional[int] = None  # None -> ceil(input_dim / 16)
        in_weight_partition: PartitionSpecLike = ("data", "model")
        out_weight_partition: PartitionSpecLike = ("model", "data")
        hidden_partition: PartitionSpecLike = (("pod", "data"), None, "model")
        # Chunked selective scan: parallel (associative) within a chunk,
        # sequential across chunks, chunk bodies rematerialized — bounds the
        # fp32 (B, chunk, d_inner, N) working set instead of materializing
        # log-depth (B, S, d_inner, N) buffers.
        scan_chunk_size: int = 256

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config
        if cfg.dt_rank is None:
            cfg.set(dt_rank=-(-cfg.input_dim // 16))

    @property
    def _d_inner(self) -> int:
        return self.config.expand * self.config.input_dim

    def _create_layer_parameter_specs(self):
        cfg = self.config
        d, di, n, r, w = (cfg.input_dim, self._d_inner, cfg.state_dim,
                          cfg.dt_rank, cfg.conv_width)
        return {
            "in_proj": ParameterSpec((d, 2 * di), cfg.param_dtype, fan_in_init(),
                                     mesh_axes=cfg.in_weight_partition),
            "conv_w": ParameterSpec((w, di), cfg.param_dtype, fan_in_init(fan_in_axes=(0,)),
                                    mesh_axes=(None, "model")),
            "conv_b": ParameterSpec((di,), cfg.param_dtype, zeros_init(),
                                    mesh_axes=("model",), weight_decay_scale=0.0),
            "x_proj": ParameterSpec((di, r + 2 * n), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=("model", None)),
            "dt_proj": ParameterSpec((r, di), cfg.param_dtype,
                                     fan_in_init(fan_in_axes=(0,)),
                                     mesh_axes=(None, "model")),
            "dt_bias": ParameterSpec((di,), cfg.param_dtype, _dt_bias_init(),
                                     mesh_axes=("model",), weight_decay_scale=0.0),
            "A_log": ParameterSpec((di, n), jnp.float32, _a_log_init(),
                                   mesh_axes=("model", None), weight_decay_scale=0.0),
            "D": ParameterSpec((di,), jnp.float32,
                               lambda k, s, dt: jnp.ones(s, dt),
                               mesh_axes=("model",), weight_decay_scale=0.0),
            "out_proj": ParameterSpec((di, d), cfg.param_dtype, fan_in_init(),
                                      mesh_axes=cfg.out_weight_partition),
        }

    # ------------------------------------------------------------------ core

    def _conv_full(self, x_in: jax.Array, conv_init: jax.Array) -> jax.Array:
        """Causal depthwise conv over (B, S, di), seeded with ``conv_init``
        (the previous W-1 inputs; zeros for a fresh sequence)."""
        W = self.config.conv_width
        x_pad = jnp.concatenate([conv_init.astype(x_in.dtype), x_in], axis=1)
        w = self.state["conv_w"].astype(x_in.dtype)  # (W, di)
        # Sum of shifted slices: cheap + layout-friendly for small W.
        S = x_in.shape[1]
        out = sum(x_pad[:, i:i + S] * w[i] for i in range(W))
        return out + self.state["conv_b"].astype(x_in.dtype)

    def _ssm_params(self, x_conv: jax.Array):
        cfg = self.config
        n, r = cfg.state_dim, cfg.dt_rank
        proj = x_conv @ self.state["x_proj"].astype(x_conv.dtype)
        dt_in, B_mat, C_mat = jnp.split(proj, [r, r + n], axis=-1)
        dt = dt_in @ self.state["dt_proj"].astype(x_conv.dtype)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.state["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(self.state["A_log"])  # (di, n)
        a_bar = jnp.exp(dt[..., None] * A)  # (B,S,di,n)
        bx = (dt * x_conv.astype(jnp.float32))[..., None] * B_mat.astype(jnp.float32)[..., None, :]
        return a_bar, bx, C_mat.astype(jnp.float32)

    @staticmethod
    def _combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, a_r * b_l + b_r

    def _scan_chunk(self, h, xc, valid=None):
        """One chunk: derive SSM params from x_conv, parallel-prefix within
        the chunk, contract to y immediately (the (B,C,di,N) states never
        leave the chunk). ``valid`` (optional (1|B, C) bool) turns padding
        steps into identity transitions (decay 1, input 0) so bucket-padded
        prefill leaves the recurrent state exact."""
        a_bar, bx, C_mat = self._ssm_params(xc)
        if valid is not None:
            a_bar = jnp.where(valid[..., None, None], a_bar, 1.0)
            bx = jnp.where(valid[..., None, None], bx, 0.0)
        bx = bx.at[:, 0].add(a_bar[:, 0] * h)
        _, h_all = jax.lax.associative_scan(self._combine, (a_bar, bx), axis=1)
        y = jnp.einsum("bsdn,bsn->bsd", h_all, C_mat)
        return h_all[:, -1], y

    def _run(self, x: jax.Array, h0: jax.Array, conv_init: jax.Array,
             valid: Optional[jax.Array] = None,
             length: Optional[jax.Array] = None):
        """Returns (y, h_final, conv_tail). With ``valid``/``length`` set,
        only the first ``length`` tokens update the recurrence and the conv
        tail is taken at the valid frontier (bucket-padded admission)."""
        cfg = self.config
        x = self._to_compute(x)
        xz = x @ self.state["in_proj"].astype(x.dtype)
        # Constrain BEFORE the split so neither half (nor their backward
        # cotangents) ever exists model-replicated.
        xz = self._shard(xz, cfg.hidden_partition)
        x_in, z = jnp.split(xz, 2, axis=-1)
        x_in = self._shard(x_in, cfg.hidden_partition)
        z = self._shard(z, cfg.hidden_partition)
        x_conv = jax.nn.silu(self._conv_full(x_in, conv_init))

        B, S, di = x_conv.shape
        C = cfg.scan_chunk_size
        if S % C != 0 or S <= C:
            h_final, y = self._scan_chunk(h0, x_conv, valid)
        else:
            n = S // C
            xs = jnp.moveaxis(x_conv.reshape(B, n, C, di), 1, 0)
            # Re-constrain after reshape/moveaxis: these xs are saved as scan
            # residuals for the whole backward — unconstrained they end up
            # model-replicated (2.1 GB/layer at jamba scale).
            hp = self.config.hidden_partition
            if hp:
                xs = self._shard(xs, (None,) + tuple(hp))
            if valid is not None:
                # Masked admission prefill goes through the same chunked
                # scan — long buckets must not materialize (B,S,di,N) states.
                Bv = valid.shape[0]
                vs = jnp.moveaxis(valid.reshape(Bv, n, C), 1, 0)
                body = jax.checkpoint(
                    lambda h, xv: self._scan_chunk(h, xv[0], xv[1]),
                    prevent_cse=False)
                h_final, ys = jax.lax.scan(body, h0, (xs, vs))
            else:
                body = jax.checkpoint(self._scan_chunk, prevent_cse=False)
                h_final, ys = jax.lax.scan(body, h0, xs)
            y = jnp.moveaxis(ys, 0, 1).reshape(B, S, di)

        y = y + self.state["D"] * x_conv.astype(jnp.float32)
        y = y.astype(x.dtype) * jax.nn.silu(z)
        y = remat_name(y, "mixer_out")
        out = y @ self.state["out_proj"].astype(x.dtype)

        W = cfg.conv_width
        tail_src = jnp.concatenate([conv_init.astype(x_in.dtype), x_in], axis=1)
        if W <= 1:
            conv_tail = tail_src[:, :0]
        elif length is None:
            conv_tail = tail_src[:, -(W - 1):]
        else:
            # Last W-1 inputs before the valid frontier: token p of x_in sits
            # at tail_src index (W-1)+p, so the window starts at ``length``.
            conv_tail = jax.lax.dynamic_slice_in_dim(tail_src, length, W - 1,
                                                     axis=1)
        return out, h_final, conv_tail

    # ------------------------------------------------------------- interface

    def forward(self, x: jax.Array, positions: Optional[jax.Array] = None) -> jax.Array:
        B = x.shape[0]
        h0 = jnp.zeros((B, self._d_inner, self.config.state_dim), jnp.float32)
        conv0 = jnp.zeros((B, self.config.conv_width - 1, self._d_inner), x.dtype)
        y, _, _ = self._run(x, h0, conv0)
        return y

    @no_context
    def state_partition_specs(self, *_):
        b = self.config.hidden_partition[0] if self.config.hidden_partition else None
        return {"h": (b, "model", None), "conv": (b, None, "model"), "index": (b,)}

    def init_states(self, batch_size: int, max_len: int) -> Dict[str, Any]:
        cfg = self.config
        return {
            "h": jnp.zeros((batch_size, self._d_inner, cfg.state_dim), jnp.float32),
            "conv": jnp.zeros((batch_size, cfg.conv_width - 1, self._d_inner),
                              jnp.bfloat16),
            "index": jnp.zeros((batch_size,), jnp.int32),
        }

    def prefill(self, state, x, positions=None, length=None):
        if length is None:
            y, h, conv = self._run(x, state["h"], state["conv"])
            new_index = state["index"] + x.shape[1]
        else:
            length = jnp.asarray(length, jnp.int32)
            valid = (jnp.arange(x.shape[1]) < length)[None, :]
            y, h, conv = self._run(x, state["h"], state["conv"],
                                   valid=valid, length=length)
            new_index = state["index"] + length
        return {"h": h, "conv": conv.astype(state["conv"].dtype),
                "index": new_index}, y

    def extend_step(self, state, x_step):
        """Sequential decode for S' >= 1 tokens (scan over steps)."""
        cfg = self.config
        x_step = self._to_compute(x_step)
        B, S_new, _ = x_step.shape
        x_in, z = jnp.split(x_step @ self.state["in_proj"].astype(x_step.dtype), 2, axis=-1)

        conv_w = self.state["conv_w"].astype(x_step.dtype)
        conv_b = self.state["conv_b"].astype(x_step.dtype)

        def step(carry, xt):
            h, conv = carry  # (B,di,n), (B,W-1,di)
            x_t, z_t = xt  # (B,di)
            window = jnp.concatenate([conv, x_t[:, None]], axis=1)  # (B,W,di)
            xc = jnp.einsum("bwd,wd->bd", window, conv_w) + conv_b
            xc = jax.nn.silu(xc)
            a_bar, bx, C_mat = self._ssm_params(xc[:, None])  # S=1
            a1, b1, c1 = a_bar[:, 0], bx[:, 0], C_mat[:, 0]
            h = a1 * h + b1
            y = jnp.einsum("bdn,bn->bd", h, c1) + self.state["D"] * xc.astype(jnp.float32)
            y = y.astype(x_t.dtype) * jax.nn.silu(z_t)
            new_conv = window[:, 1:].astype(conv.dtype)
            return (h, new_conv), y

        (h, conv), ys = jax.lax.scan(
            step,
            (state["h"], state["conv"].astype(x_step.dtype)),
            (jnp.moveaxis(x_in, 1, 0), jnp.moveaxis(z, 1, 0)))
        y = jnp.moveaxis(ys, 0, 1) @ self.state["out_proj"].astype(x_step.dtype)
        return {"h": h, "conv": conv.astype(state["conv"].dtype),
                "index": state["index"] + S_new}, y
