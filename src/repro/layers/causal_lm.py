"""Model heads: CausalLM (train + serve) and MaskedLM (encoder, HuBERT-style).

The model consumes a *batch dict* so heterogeneous modalities stay config:
  input_ids         (B, S) int32            — text tokens
  labels            (B, S) int32            — next-token targets, -100 = ignore
  input_embeddings  (B, P, D) or (B, S, D)  — stub frontend outputs (VLM/audio)
  mask_positions    (B, S) bool             — MaskedLM corruption mask
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, ConfigBase, Required, config_class, maybe_set
from repro.core.module import no_context
from repro.layers.base import BaseLayer, ParameterSpec, normal_init
from repro.layers.transformer import Decoder

__all__ = ["CausalLM", "MaskedLM", "cross_entropy"]

IGNORE_TARGET = -100


def cross_entropy(logits: jax.Array, labels: jax.Array, *, z_loss_scale: float = 0.0
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean CE over valid (label >= 0) positions, fp32, optional z-loss."""
    logits = logits.astype(jnp.float32)
    valid = labels != IGNORE_TARGET
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = logz - label_logits
    if z_loss_scale > 0.0:
        nll = nll + z_loss_scale * jnp.square(logz)
    denom = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / denom
    acc = jnp.sum(jnp.where(valid, (jnp.argmax(logits, -1) == safe_labels), 0)) / denom
    return loss, {"accuracy": acc, "num_targets": denom}


class CausalLM(BaseLayer):
    """decoder + CE loss; aux losses (MoE balance) surface via the
    InvocationContext — this layer never references MoE."""

    @config_class
    class Config(BaseLayer.Config):
        decoder: Required[ConfigBase] = REQUIRED
        z_loss_scale: float = 0.0
        # Token-chunked CE: never materializes (B, S, V) logits — required to
        # fit 256k-vocab training at 1M tokens/step. None = single-shot.
        loss_chunk_size: Optional[int] = None

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        decoder = cfg.decoder.clone()
        if "dtype_policy" in decoder.keys():
            maybe_set(decoder, dtype_policy=cfg.dtype_policy)
        self._add_child("decoder", decoder)

    def forward(self, batch: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
        cfg = self.config
        S = batch["labels"].shape[1]
        if cfg.loss_chunk_size and S % cfg.loss_chunk_size == 0 \
                and S > cfg.loss_chunk_size:
            return self._chunked_forward(batch)
        logits = self.decoder(
            batch.get("input_ids"),
            input_embeddings=batch.get("input_embeddings"),
            positions=batch.get("positions"),
        )
        with jax.named_scope("loss"):
            loss, metrics = cross_entropy(
                logits, batch["labels"], z_loss_scale=self.config.z_loss_scale)
        self.add_summary("loss", loss)
        self.add_summary("accuracy", metrics["accuracy"])
        return loss, {"logits": logits, **metrics}

    def _chunked_forward(self, batch):
        """CE over sequence chunks: logits live one chunk at a time (fwd AND
        bwd via remat)."""
        cfg = self.config
        c = cfg.loss_chunk_size
        h = self.decoder.hidden(
            batch.get("input_ids"),
            input_embeddings=batch.get("input_embeddings"),
            positions=batch.get("positions"),
        )
        B, S, D = h.shape
        n = S // c
        hs = jnp.moveaxis(h.reshape(B, n, c, D), 1, 0)  # (n, B, c, D)
        labels = jnp.moveaxis(batch["labels"].reshape(B, n, c), 1, 0)
        decoder = self.decoder

        def body(carry, xs):
            nll_sum, correct, count = carry
            h_c, l_c = xs
            logits = decoder.head(h_c)
            with jax.named_scope("loss"):
                logits = logits.astype(jnp.float32)
                valid = l_c != IGNORE_TARGET
                safe = jnp.where(valid, l_c, 0)
                logz = jax.nn.logsumexp(logits, axis=-1)
                lab = jnp.take_along_axis(
                    logits, safe[..., None], axis=-1)[..., 0]
                nll = logz - lab
                if cfg.z_loss_scale > 0.0:
                    nll = nll + cfg.z_loss_scale * jnp.square(logz)
                nll_sum = nll_sum + jnp.sum(jnp.where(valid, nll, 0.0))
                correct = correct + jnp.sum(
                    jnp.where(valid, jnp.argmax(logits, -1) == safe, 0))
                count = count + jnp.sum(valid)
            return (nll_sum, correct, count), None

        body = jax.checkpoint(body, prevent_cse=False)
        (nll_sum, correct, count), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
                   jnp.zeros((), jnp.int32)), (hs, labels))
        denom = jnp.maximum(count, 1)
        loss = nll_sum / denom
        acc = correct / denom
        self.add_summary("loss", loss)
        self.add_summary("accuracy", acc)
        return loss, {"logits": None, "accuracy": acc, "num_targets": denom}

    def predict(self, batch: Dict[str, Any]) -> jax.Array:
        return self.decoder(
            batch.get("input_ids"),
            input_embeddings=batch.get("input_embeddings"),
            positions=batch.get("positions"),
        )

    # --- serving ----------------------------------------------------------------

    @no_context
    def state_partition_specs(self, *_):
        return self.decoder.state_partition_specs()

    def init_states(self, batch_size: int, max_len: int):
        return self.decoder.init_states(batch_size, max_len)

    def prefill(self, state, input_ids=None, *, input_embeddings=None,
                length=None):
        """``length`` (optional scalar): number of real prompt tokens; the
        rest of ``input_ids`` is bucket padding that must not enter any
        layer's cache/recurrent state (continuous-batching admission)."""
        return self.decoder.prefill(
            state, input_ids, input_embeddings=input_embeddings, length=length)

    def extend_step(self, state, ids_step):
        return self.decoder.extend_step(state, ids_step)


class MaskedLM(BaseLayer):
    """Encoder-only masked-prediction model (HuBERT backbone).

    Frame embeddings from the (stubbed) conv frontend are corrupted at
    ``mask_positions`` with a learned vector; loss is CE at masked positions.
    """

    @config_class
    class Config(BaseLayer.Config):
        decoder: Required[ConfigBase] = REQUIRED  # configured bidirectional
        dim: Required[int] = REQUIRED

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        decoder = cfg.decoder.clone()
        if "dtype_policy" in decoder.keys():
            maybe_set(decoder, dtype_policy=cfg.dtype_policy)
        self._add_child("decoder", decoder)

    def _create_layer_parameter_specs(self):
        return {"mask_emb": ParameterSpec(
            (self.config.dim,), self.config.param_dtype, normal_init(0.02))}

    def forward(self, batch: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
        x = batch["input_embeddings"]
        mask = batch["mask_positions"]
        x = jnp.where(mask[..., None], self.state["mask_emb"].astype(x.dtype), x)
        logits = self.decoder(None, input_embeddings=x)
        labels = jnp.where(mask, batch["labels"], IGNORE_TARGET)
        with jax.named_scope("loss"):
            loss, metrics = cross_entropy(logits, labels)
        self.add_summary("loss", loss)
        return loss, {"logits": logits, **metrics}

    def predict(self, batch: Dict[str, Any]) -> jax.Array:
        return self.decoder(None, input_embeddings=batch["input_embeddings"])
