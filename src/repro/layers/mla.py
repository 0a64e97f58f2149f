"""Multi-head latent attention (MLA), DeepSeek-V2/V3 style, without a query
LoRA (as Moonlight-16B-A3B: ``q_lora_rank`` null) [arXiv:2405.04434].

  q            = x W_q                  -> H x (nope + rope)
  [c_kv, k_pe] = x W_kva                c_kv: the KV latent, k_pe: rope wide
  c_kv         = RMSNorm(c_kv)
  [k_nope, v]  = c_kv W_kvb             -> H x (nope + v)
  q_pe, k_pe   = RoPE(q_pe), RoPE(k_pe) k_pe is one head, shared by all
  k, q         = [k_nope, k_pe], [q_nope, q_pe]
  out          = softmax(q k^T / sqrt(nope + rope)) v W_o   (causal)

Training runs this expanded (non-absorbed) form through the same attention
kernels as :class:`MultiheadAttention`: the flash kernels take a value head
dim (v_head_dim) that differs from the q/k head dim (nope + rope). The
layer is a training-path token mixer: it has no KV cache.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, ConfigBase, Required, config_class, maybe_set
from repro.core.utils import remat_name
from repro.kernels import ops as kernel_ops
from repro.layers.base import BaseLayer, KernelConfig
from repro.layers.basic import Linear, RMSNorm
from repro.layers.rope import BaseRotaryEmbedding, PairRotaryEmbedding

__all__ = ["LatentAttention"]

# (B, S, H*D) activations: batch over the data axes, heads over "model".
_HIDDEN = (("pod", "data"), None, "model")


class LatentAttention(BaseLayer):
    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        num_heads: Required[int] = REQUIRED
        qk_nope_head_dim: int = 128
        qk_rope_head_dim: int = 64
        v_head_dim: int = 128
        kv_lora_rank: int = 512
        proj: ConfigBase = Linear.Config()
        # The KV latent's norm (DeepSeek's ``kv_a_layernorm``).
        kv_norm: ConfigBase = RMSNorm.Config()
        # RoPE on the rope slice of q and k; its dim is qk_rope_head_dim.
        rope: BaseRotaryEmbedding.Config = PairRotaryEmbedding.Config()
        kernel: KernelConfig = KernelConfig()

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config
        H = cfg.num_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim

        def proj(din, dout, partition):
            return maybe_set(cfg.proj.clone().set(
                input_dim=din, output_dim=dout, bias=False,
                weight_partition=partition, param_dtype=cfg.param_dtype),
                dtype_policy=cfg.dtype_policy)

        self._add_child("q_proj", proj(cfg.input_dim, H * qk,
                                       ("data", "model")))
        self._add_child("kv_a_proj", proj(
            cfg.input_dim, cfg.kv_lora_rank + cfg.qk_rope_head_dim,
            ("data", None)))
        self._add_child("kv_norm", maybe_set(
            cfg.kv_norm.clone().set(input_dim=cfg.kv_lora_rank),
            dtype_policy=cfg.dtype_policy))
        self._add_child("kv_b_proj", proj(
            cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            (None, "model")))
        self._add_child("o_proj", proj(H * cfg.v_head_dim, cfg.input_dim,
                                       ("model", "data")))
        self._add_child("rope", cfg.rope.clone().set(dim=cfg.qk_rope_head_dim))

    def forward(self, x: jax.Array, positions: Optional[jax.Array] = None
                ) -> jax.Array:
        cfg = self.config
        x = self._to_compute(x)
        B, S, _ = x.shape
        H, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        if positions is None:
            positions = jnp.arange(S)
        q = self._shard(self.q_proj(x), _HIDDEN)
        q = q.reshape(B, S, H, nope + rope)
        kva = self.kv_a_proj(x)
        c_kv = self.kv_norm(kva[..., :cfg.kv_lora_rank])
        k_pe = kva[..., cfg.kv_lora_rank:].reshape(B, S, 1, rope)
        kv = self._shard(self.kv_b_proj(c_kv), _HIDDEN)
        kv = kv.reshape(B, S, H, nope + cfg.v_head_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_pe = self.rope.apply(q[..., nope:], positions)
        k_pe = self.rope.apply(k_pe, positions)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, S, H, rope))], axis=-1)
        q = remat_name(q, "q_proj")
        k = remat_name(k, "kv_proj")
        out = kernel_ops.flash_attention(
            q, k, v, causal=True, scale=(nope + rope) ** -0.5,
            kernel=self.kernel_config, needs_grad=self.is_training,
            partition=(*_HIDDEN, None))
        out = remat_name(out, "attn_out")
        out = self._shard(out.reshape(B, S, H * cfg.v_head_dim), _HIDDEN)
        return self.o_proj(out)
