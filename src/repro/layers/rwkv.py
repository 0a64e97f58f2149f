"""RWKV6 ("Finch") — attention-free token mixing with data-dependent decay.

The WKV6 core (chunked parallel form for train/prefill, O(1) recurrent state
for decode) lives in ``repro.kernels`` with ref oracle + Pallas kernel; this
module provides the surrounding projections (token-shift lerps, decay LoRA,
per-head group norm, output gate) and the standard token-mixer interface.

An RWKV block's channel-mix FFN is *also* stateful (token shift), so the
block implements the full interface itself rather than reusing
TransformerLayer — still pure composition.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, ConfigBase, Required, config_class, maybe_set
from repro.core.module import no_context
from repro.core.utils import PartitionSpecLike, remat_name
from repro.kernels import ops as kernel_ops
from repro.layers.base import (
    BaseLayer,
    KernelConfig,
    ParameterSpec,
    fan_in_init,
    normal_init,
    ones_init,
    zeros_init,
)
from repro.layers.basic import LayerNorm

__all__ = ["RWKV6TimeMix", "RWKV6ChannelMix", "RWKV6Block"]


def _token_shift(x: jax.Array, prev: Optional[jax.Array] = None) -> jax.Array:
    """x_{t-1}; position 0 takes ``prev`` (zeros for a fresh sequence)."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([prev.astype(x.dtype), x[:, :-1]], axis=1)


class RWKV6TimeMix(BaseLayer):
    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        head_dim: int = 64
        decay_lora_dim: int = 64
        proj_weight_partition: PartitionSpecLike = ("data", "model")
        out_weight_partition: PartitionSpecLike = ("model", "data")
        hidden_partition: PartitionSpecLike = (("pod", "data"), None, "model")
        # Registry dispatch for the "wkv6" op (paper §4.2); wkv_chunk_size
        # tiling also lives on the shared KernelConfig.
        kernel: KernelConfig = KernelConfig()

    @property
    def _num_heads(self) -> int:
        return self.config.input_dim // self.config.head_dim

    def _create_layer_parameter_specs(self):
        cfg = self.config
        d, hd, H, r = cfg.input_dim, cfg.head_dim, self._num_heads, cfg.decay_lora_dim
        near_one = lambda: (lambda k, s, dt: jnp.full(s, 0.5, dt))  # noqa: E731
        return {
            # Token-shift lerp coefficients for r,k,v,w,g.
            "mu": ParameterSpec((5, d), cfg.param_dtype, near_one(),
                                weight_decay_scale=0.0),
            "r_proj": ParameterSpec((d, d), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=cfg.proj_weight_partition),
            "k_proj": ParameterSpec((d, d), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=cfg.proj_weight_partition),
            "v_proj": ParameterSpec((d, d), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=cfg.proj_weight_partition),
            "g_proj": ParameterSpec((d, d), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=cfg.proj_weight_partition),
            # Data-dependent decay: w = exp(-exp(w0 + tanh(x@w1)@w2)).
            "w0": ParameterSpec((d,), jnp.float32,
                                lambda k, s, dt: jnp.full(s, -1.0, dt),
                                weight_decay_scale=0.0),
            "w1": ParameterSpec((d, r), cfg.param_dtype, normal_init(0.02),
                                mesh_axes=("data", None)),
            "w2": ParameterSpec((r, d), cfg.param_dtype, normal_init(0.02),
                                mesh_axes=(None, "model")),
            # Per-head current-token bonus.
            "u": ParameterSpec((H, hd), jnp.float32, normal_init(0.5),
                               weight_decay_scale=0.0),
            # Per-head group norm on the wkv output.
            "ln_scale": ParameterSpec((d,), cfg.param_dtype, ones_init(),
                                      weight_decay_scale=0.0),
            "ln_bias": ParameterSpec((d,), cfg.param_dtype, zeros_init(),
                                     weight_decay_scale=0.0),
            "out_proj": ParameterSpec((d, d), cfg.param_dtype, fan_in_init(),
                                      mesh_axes=cfg.out_weight_partition),
        }

    def _projections(self, x: jax.Array, shift_prev: Optional[jax.Array]):
        cfg = self.config
        x = self._to_compute(x)
        B, S, d = x.shape
        H, hd = self._num_heads, cfg.head_dim
        xs = _token_shift(x, shift_prev)
        mu = self.state["mu"].astype(x.dtype)  # (5, d)
        mixed = [x + (xs - x) * mu[i] for i in range(5)]
        m_r, m_k, m_v, m_w, m_g = mixed
        r = (m_r @ self.state["r_proj"].astype(x.dtype)).reshape(B, S, H, hd)
        k = (m_k @ self.state["k_proj"].astype(x.dtype)).reshape(B, S, H, hd)
        v = (m_v @ self.state["v_proj"].astype(x.dtype)).reshape(B, S, H, hd)
        g = jax.nn.silu(m_g @ self.state["g_proj"].astype(x.dtype))
        lora = jnp.tanh(m_w.astype(jnp.float32) @ self.state["w1"].astype(jnp.float32))
        logw = self.state["w0"] + lora @ self.state["w2"].astype(jnp.float32)
        w = jnp.exp(-jnp.exp(logw)).reshape(B, S, H, hd)  # in (0,1)
        return r, k, v, w, g

    def _group_norm(self, y: jax.Array) -> jax.Array:
        """LayerNorm within each head."""
        cfg = self.config
        B, S, H, hd = y.shape
        yf = y.astype(jnp.float32)
        mean = jnp.mean(yf, axis=-1, keepdims=True)
        var = jnp.var(yf, axis=-1, keepdims=True)
        yn = (yf - mean) * jax.lax.rsqrt(var + 1e-5)
        yn = yn.reshape(B, S, H * hd)
        yn = yn * self.state["ln_scale"].astype(jnp.float32) + \
            self.state["ln_bias"].astype(jnp.float32)
        return yn

    def _wkv(self, r, k, v, w, state):
        return kernel_ops.wkv6(r, k, v, w, self.state["u"], state,
                               kernel=self.kernel_config,
                               needs_grad=self.is_training)

    def forward(self, x: jax.Array, positions: Optional[jax.Array] = None) -> jax.Array:
        x = self._to_compute(x)
        r, k, v, w, g = self._projections(x, None)
        out, _ = self._wkv(r, k, v, w, None)
        out = remat_name(out, "mixer_out")
        y = self._group_norm(out).astype(x.dtype) * g
        return y @ self.state["out_proj"].astype(x.dtype)

    @no_context
    def state_partition_specs(self, *_):
        b = self.config.hidden_partition[0] if self.config.hidden_partition else None
        return {"shift": (b, None, "model"), "wkv": (b, "model", None, None),
                "index": (b,)}

    def init_states(self, batch_size: int, max_len: int) -> Dict[str, Any]:
        cfg = self.config
        H, hd = self._num_heads, cfg.head_dim
        return {
            "shift": jnp.zeros((batch_size, 1, cfg.input_dim), jnp.bfloat16),
            "wkv": jnp.zeros((batch_size, H, hd, hd), jnp.float32),
            "index": jnp.zeros((batch_size,), jnp.int32),
        }

    def prefill(self, state, x, positions=None, length=None):
        x = self._to_compute(x)
        r, k, v, w, g = self._projections(x, state["shift"])
        if length is not None:
            # Bucket padding must leave the wkv state exact: an invalid step
            # with decay w=1 and key k=0 is the identity transition
            # (s <- 1*s + 0*v^T, zero bonus).
            length = jnp.asarray(length, jnp.int32)
            valid = (jnp.arange(x.shape[1]) < length)[None, :, None, None]
            k = jnp.where(valid, k, 0.0)
            w = jnp.where(valid, w, 1.0)
        out, wkv_state = self._wkv(r, k, v, w, state["wkv"])
        y = self._group_norm(out).astype(x.dtype) * g
        y = y @ self.state["out_proj"].astype(x.dtype)
        if length is None:
            shift = x[:, -1:]
            new_index = state["index"] + x.shape[1]
        else:
            shift = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
            new_index = state["index"] + length
        new_state = {"shift": shift.astype(state["shift"].dtype),
                     "wkv": wkv_state, "index": new_index}
        return new_state, y

    def extend_step(self, state, x_step):
        x_step = self._to_compute(x_step)
        r, k, v, w, g = self._projections(x_step, state["shift"])
        out, wkv_state = kernel_ops.wkv6_decode(
            r, k, v, w, self.state["u"], state["wkv"],
            kernel=self.kernel_config)
        y = self._group_norm(out).astype(x_step.dtype) * g
        y = y @ self.state["out_proj"].astype(x_step.dtype)
        new_state = {"shift": x_step[:, -1:].astype(state["shift"].dtype),
                     "wkv": wkv_state, "index": state["index"] + x_step.shape[1]}
        return new_state, y


class RWKV6ChannelMix(BaseLayer):
    """RWKV's FFN — stateful via token shift."""

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        hidden_dim: Required[int] = REQUIRED
        up_weight_partition: PartitionSpecLike = ("data", "model")
        down_weight_partition: PartitionSpecLike = ("model", "data")
        state_partition: PartitionSpecLike = (("pod", "data"), None, "model")

    @no_context
    def state_partition_specs(self, *_):
        return {"shift": self.config.state_partition}

    def _create_layer_parameter_specs(self):
        cfg = self.config
        d, h = cfg.input_dim, cfg.hidden_dim
        half = lambda: (lambda k, s, dt: jnp.full(s, 0.5, dt))  # noqa: E731
        return {
            "mu": ParameterSpec((2, d), cfg.param_dtype, half(), weight_decay_scale=0.0),
            "k_proj": ParameterSpec((d, h), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=cfg.up_weight_partition),
            "v_proj": ParameterSpec((h, d), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=cfg.down_weight_partition),
            "r_proj": ParameterSpec((d, d), cfg.param_dtype, fan_in_init(),
                                    mesh_axes=("data", "model")),
        }

    def _core(self, x, shift_prev):
        x = self._to_compute(x)
        mu = self.state["mu"].astype(x.dtype)
        xs = _token_shift(x, shift_prev)
        xk = x + (xs - x) * mu[0]
        xr = x + (xs - x) * mu[1]
        k = jnp.square(jax.nn.relu(xk @ self.state["k_proj"].astype(x.dtype)))
        k = remat_name(k, "ffn_hidden")
        r = jax.nn.sigmoid(xr @ self.state["r_proj"].astype(x.dtype))
        return r * (k @ self.state["v_proj"].astype(x.dtype))

    def forward(self, x, positions=None):
        return self._core(x, None)

    def init_states(self, batch_size, max_len):
        return {"shift": jnp.zeros((batch_size, 1, self.config.input_dim), jnp.bfloat16)}

    def prefill(self, state, x, positions=None, length=None):
        y = self._core(x, state["shift"])
        if length is None:
            shift = x[:, -1:]
        else:
            shift = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(length, jnp.int32) - 1, 1, axis=1)
        return {"shift": shift.astype(state["shift"].dtype)}, y

    def extend_step(self, state, x_step):
        y = self._core(x_step, state["shift"])
        return {"shift": x_step[:, -1:].astype(state["shift"].dtype)}, y


class RWKV6Block(BaseLayer):
    """ln -> time_mix -> residual; ln -> channel_mix -> residual."""

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        time_mix: RWKV6TimeMix.Config = RWKV6TimeMix.Config()
        channel_mix: RWKV6ChannelMix.Config = RWKV6ChannelMix.Config()
        norm: ConfigBase = LayerNorm.Config()
        activation_partition: PartitionSpecLike = (("pod", "data"), None, "model")

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config

        def with_dim(c):
            c = c.clone()
            if "input_dim" in c.keys() and not c.input_dim:
                c.set(input_dim=cfg.input_dim)
            if "dtype_policy" in c.keys():
                maybe_set(c, dtype_policy=cfg.dtype_policy)
            return c

        self._add_child("ln1", with_dim(cfg.norm))
        self._add_child("time_mix", with_dim(cfg.time_mix))
        self._add_child("ln2", with_dim(cfg.norm))
        self._add_child("channel_mix", with_dim(cfg.channel_mix))

    @no_context
    def state_partition_specs(self, *_):
        return {"tm": self.time_mix.state_partition_specs(),
                "cm": self.channel_mix.state_partition_specs()}

    def forward(self, x, positions=None):
        x = self._to_compute(x)
        x = self._shard(x, self.config.activation_partition)
        x = x + self.time_mix(self.ln1(x), positions=positions)
        x = x + self.channel_mix(self.ln2(x))
        return self._shard(x, self.config.activation_partition)  # scan carry

    def init_states(self, batch_size, max_len):
        return {"tm": self.time_mix.init_states(batch_size, max_len),
                "cm": self.channel_mix.init_states(batch_size, max_len)}

    def prefill(self, state, x, positions=None, length=None):
        tm_state, h = self.time_mix.prefill(
            state["tm"], self.ln1(x), positions=positions, length=length)
        x = x + h
        cm_state, h2 = self.channel_mix.prefill(state["cm"], self.ln2(x),
                                                length=length)
        return {"tm": tm_state, "cm": cm_state}, x + h2

    def extend_step(self, state, x_step):
        tm_state, h = self.time_mix.extend_step(state["tm"], self.ln1(x_step))
        x = x_step + h
        cm_state, h2 = self.channel_mix.extend_step(state["cm"], self.ln2(x))
        return {"tm": tm_state, "cm": cm_state}, x + h2
