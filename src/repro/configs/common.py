"""Shared config builders for the assigned architectures.

Every architecture is a *config program* over the layer library — no
model-specific layer classes exist anywhere (the paper's central claim).
Builders only choose child configs and dims; sharding defaults adapt to
divisibility against the production mesh (model axis = 16).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.config import ConfigBase
from repro.layers import (
    CausalLM,
    Decoder,
    FeedForward,
    MaskedLM,
    MultiheadAttention,
    Repeat,
    RMSNorm,
    TransformerLayer,
)
from repro.layers.basic import LayerNorm, Linear
from repro.layers.moe import MoELayer, ResidualMoE
from repro.layers.rope import RotaryEmbedding
from repro.layers.rwkv import RWKV6Block
from repro.layers.ssm import MambaMixer
from repro.layers.transformer import Block

MODEL_AXIS = 16  # production mesh model-axis size


def kv_cache_spec(num_kv_heads: int, head_dim: int):
    """(B, Hkv, T, D) cache sharding: heads on "model" when divisible;
    otherwise shard the SEQUENCE dim over "model" (flash-decoding layout —
    per-shard partial softmax, GSPMD inserts the combine)."""
    if num_kv_heads % MODEL_AXIS == 0:
        return (("pod", "data"), "model", None, None)
    return (("pod", "data"), None, "model", None)


def expert_specs(num_experts: int):
    """MoE (E, D, H) weight + dispatch sharding: expert parallelism over
    "model" when divisible (jamba 16e, arctic 128e); otherwise replicate E
    and tensor-shard the expert hidden dim (mixtral 8e)."""
    if num_experts % MODEL_AXIS == 0:
        return dict(
            up_weight_partition=("model", "data", None),
            down_weight_partition=("model", None, "data"),
            dispatch_partition=(("pod", "data"), None, "model", None),
            expert_partition=("model", ("pod", "data"), None, None),
        )
    return dict(
        up_weight_partition=(None, "data", "model"),
        down_weight_partition=(None, "model", "data"),
        dispatch_partition=(("pod", "data"), None, None, None),
        expert_partition=(None, ("pod", "data"), None, "model"),
    )


def attention_cfg(
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: Optional[int] = None,
    qkv_bias: bool = False,
    rope_theta: Optional[float] = 10000.0,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    query_scale: Optional[float] = None,
) -> MultiheadAttention.Config:
    cfg = MultiheadAttention.default_config().set(
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        qkv_bias=qkv_bias,
        causal=causal,
        sliding_window=sliding_window,
        logit_softcap=logit_softcap,
        query_scale=query_scale,
    )
    if head_dim is not None:
        cfg.set(head_dim=head_dim)
    if rope_theta is None:
        cfg.set(rope=None)
    else:
        cfg.rope = RotaryEmbedding.default_config().set(theta=rope_theta)
    return cfg


def ffn_cfg(hidden_dim: int, activation=("linear", "nn.silu")) -> FeedForward.Config:
    return FeedForward.default_config().set(hidden_dim=hidden_dim,
                                            activation=activation)


def moe_cfg(hidden_dim: int, num_experts: int, top_k: int = 2,
            capacity_factor: float = 2.0,
            activation=("linear", "nn.silu")) -> MoELayer.Config:
    return MoELayer.default_config().set(
        hidden_dim=hidden_dim, num_experts=num_experts, top_k=top_k,
        capacity_factor=capacity_factor, activation=activation,
        **expert_specs(num_experts))


def layer_cfg(
    dim: int,
    attention: ConfigBase,
    feed_forward: ConfigBase,
    *,
    norm: Optional[ConfigBase] = None,
    post_norms: bool = False,
) -> TransformerLayer.Config:
    cfg = TransformerLayer.default_config().set(
        input_dim=dim,
        self_attention=attention,
        feed_forward=feed_forward,
        use_post_attention_norm=post_norms,
        use_post_ffn_norm=post_norms,
    )
    if attention is not None and "kv_cache_partition" in attention.keys():
        nh = attention.num_kv_heads or attention.num_heads
        hd = attention.head_dim or dim // attention.num_heads
        attention.set(kv_cache_partition=kv_cache_spec(nh, hd))
    if norm is not None:
        cfg.norm = norm
    return cfg


def decoder_cfg(
    *,
    vocab_size: int,
    dim: int,
    stack: ConfigBase,
    tied_embeddings: bool = True,
    logits_softcap: Optional[float] = None,
    scale_embeddings: bool = False,
    final_norm: Optional[ConfigBase] = None,
) -> Decoder.Config:
    cfg = Decoder.default_config().set(
        vocab_size=vocab_size, dim=dim, stack=stack,
        logits_softcap=logits_softcap)
    # Vocab dims only shard when divisible by the model axis (hubert: 504).
    vocab_ok = vocab_size % MODEL_AXIS == 0
    cfg.emb.set(scale_by_sqrt_dim=scale_embeddings,
                weight_partition=("model", "data") if vocab_ok else (None, "model"))
    cfg.set(logits_partition=(("pod", "data"), None, "model" if vocab_ok else None))
    if not tied_embeddings:
        cfg.lm_head = Linear.default_config().set(
            weight_partition=("data", "model") if vocab_ok else ("model", None))
    if final_norm is not None:
        cfg.final_norm = final_norm
    return cfg


def lm_cfg(decoder: Decoder.Config, z_loss: float = 0.0) -> CausalLM.Config:
    return CausalLM.default_config().set(name="model", decoder=decoder,
                                         z_loss_scale=z_loss)


def repeat_cfg(layer: ConfigBase, num_layers: int,
               remat: Optional[str] = "full") -> Repeat.Config:
    return Repeat.default_config().set(layer=layer, num_layers=num_layers,
                                       remat_policy=remat)


def pattern_stack_cfg(pattern: List[ConfigBase], num_blocks: int,
                      remat: Optional[str] = "full") -> Repeat.Config:
    """Repeat over a heterogeneous super-block (jamba, gemma2)."""
    block = Block.default_config().set(layers=pattern)
    return Repeat.default_config().set(layer=block, num_layers=num_blocks,
                                       remat_policy=remat)


# --------------------------------------------------------------------------
# Input shapes (assigned) + input spec helpers
# --------------------------------------------------------------------------

SHAPES: Dict[str, Dict[str, Any]] = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}


def lm_input_specs(shape: str, *, vocab_size: int, modality: str = "text",
                   model_dim: Optional[int] = None, num_patches: int = 256
                   ) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation).

    For decode shapes this is the *step* input; the KV-cache state specs are
    derived separately via eval_shape of init_states.
    """
    info = SHAPES[shape]
    B, S = info["global_batch"], info["seq_len"]
    i32 = jnp.int32
    if info["kind"] in ("train", "prefill"):
        if modality == "audio":
            specs = {
                "input_embeddings": jax.ShapeDtypeStruct((B, S, model_dim), jnp.bfloat16),
                "mask_positions": jax.ShapeDtypeStruct((B, S), jnp.bool_),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
            if info["kind"] == "prefill":
                specs.pop("labels")
                specs.pop("mask_positions")
            return specs
        specs = {"input_ids": jax.ShapeDtypeStruct((B, S), i32)}
        if info["kind"] == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
        if modality == "vlm":
            # Stub frontend: precomputed patch embeddings (assignment carve-out).
            specs["input_embeddings"] = jax.ShapeDtypeStruct(
                (B, num_patches, model_dim), jnp.bfloat16)
        return specs
    # decode: one new token against a seq_len cache
    return {"ids_step": jax.ShapeDtypeStruct((B, 1), i32)}


@dataclasses.dataclass
class ArchSpec:
    """Everything the launcher/benchmarks need to know about one arch."""

    arch_id: str
    family: str  # dense | moe | hybrid | ssm | vlm | audio
    citation: str
    make_model: Any  # () -> model config (full size)
    make_smoke: Any  # () -> reduced model config
    vocab_size: int
    model_dim: int
    modality: str = "text"
    # Shapes this arch runs, with skip reasons for the rest.
    skip_shapes: Dict[str, str] = dataclasses.field(default_factory=dict)
    # 6*N*D model flops: N = active params (MoE: routed active only).
    active_params: Optional[int] = None
    total_params: Optional[int] = None

    def input_specs(self, shape: str):
        num_patches = 256 if self.modality == "vlm" else 0
        return lm_input_specs(shape, vocab_size=self.vocab_size,
                              modality=self.modality, model_dim=self.model_dim,
                              num_patches=num_patches)

    def supports(self, shape: str) -> bool:
        return shape not in self.skip_shapes
