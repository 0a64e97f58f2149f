"""Transformer composition: TransformerLayer, Block, Repeat (scan), Decoder.

Composition rules (the paper's modularity story):
  * ``TransformerLayer.self_attention`` is ANY token mixer (attention, Mamba,
    RWKV6) — they share the forward/init_states/prefill/extend_step
    interface, so hybrid models are pure config.
  * ``TransformerLayer.feed_forward`` is ANY FFN-compatible module (dense FFN,
    MoE, residual-MoE) — MoE is a drop-in replacement (§2.1).
  * ``Repeat`` stacks identical layers (or identical heterogeneous *blocks*)
    with ``lax.scan`` over stacked params — keeping HLO size O(1) in depth.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, ConfigBase, Required, config_class, maybe_set
from repro.core.module import functional, no_context
from repro.core.utils import PartitionSpecLike, remat_name
from repro.layers.attention import MultiheadAttention
from repro.layers.base import BaseLayer, ParameterSpec
from repro.layers.basic import Dropout, Embedding, Linear, RMSNorm
from repro.layers.ffn import FeedForward

__all__ = ["TransformerLayer", "Block", "Repeat", "StackedTransformer", "Decoder"]


class TransformerLayer(BaseLayer):
    """Pre-norm residual layer: x + mixer(norm(x)); x + ffn(norm(x)).

    Optional post-norms (gemma2 'sandwich') via config flags.
    """

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        self_attention: ConfigBase = MultiheadAttention.Config()
        feed_forward: ConfigBase = FeedForward.Config()
        norm: ConfigBase = RMSNorm.Config()
        use_post_attention_norm: bool = False
        use_post_ffn_norm: bool = False
        residual_dropout: float = 0.0
        # AXLearn-style default: batch over (pod, data), embedding dim over
        # "model" — keeps scan-carry activations (the remat residuals) fully
        # sharded instead of model-axis-replicated.
        activation_partition: PartitionSpecLike = (("pod", "data"), None, "model")

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config

        def with_dim(c, field="input_dim"):
            c = c.clone()
            if field in c.keys():
                cur = getattr(c, field)
                if not cur:
                    c.set(**{field: cfg.input_dim})
            if "dtype_policy" in c.keys():
                maybe_set(c, dtype_policy=cfg.dtype_policy)
            return c

        self._add_child("attn_norm", with_dim(cfg.norm))
        self._add_child("self_attention", with_dim(cfg.self_attention))
        self._add_child("ffn_norm", with_dim(cfg.norm))
        self._add_child("feed_forward", with_dim(cfg.feed_forward))
        if cfg.use_post_attention_norm:
            self._add_child("post_attn_norm", with_dim(cfg.norm))
        if cfg.use_post_ffn_norm:
            self._add_child("post_ffn_norm", with_dim(cfg.norm))
        if cfg.residual_dropout:
            self._add_child("dropout", Dropout.default_config().set(rate=cfg.residual_dropout))

    def _maybe_dropout(self, x):
        if self.config.residual_dropout:
            return self.dropout(x)
        return x

    def _ffn_block(self, x):
        cfg = self.config
        h = self.feed_forward(self.ffn_norm(x))
        if cfg.use_post_ffn_norm:
            h = self.post_ffn_norm(h)
        return x + self._maybe_dropout(h)

    # Residual-branch interface (the reversible decomposition): forward() is
    # exactly x + attn_branch(x) followed by x + ffn_branch(x). A two-stream
    # reversible stack (repro.memopt.reversible) calls the branches WITHOUT
    # the residual adds — their presence is what marks a layer invertible.

    def attn_branch(self, x, positions: Optional[jax.Array] = None):
        """F(x) = attn(norm(x)) — the attention residual branch alone."""
        cfg = self.config
        x = self._to_compute(x)
        x = self._shard(x, cfg.activation_partition)
        h = self.self_attention(self.attn_norm(x), positions=positions)
        if cfg.use_post_attention_norm:
            h = self.post_attn_norm(h)
        return self._shard(h, cfg.activation_partition)

    def ffn_branch(self, x):
        """G(x) = ffn(norm(x)) — the feed-forward residual branch alone."""
        cfg = self.config
        x = self._to_compute(x)
        x = self._shard(x, cfg.activation_partition)
        h = self.feed_forward(self.ffn_norm(x))
        if cfg.use_post_ffn_norm:
            h = self.post_ffn_norm(h)
        return self._shard(h, cfg.activation_partition)

    def forward(self, x: jax.Array, positions: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        x = self._to_compute(x)  # residual stream runs in the compute dtype
        x = self._shard(x, cfg.activation_partition)
        h = self.self_attention(self.attn_norm(x), positions=positions)
        if cfg.use_post_attention_norm:
            h = self.post_attn_norm(h)
        x = x + self._maybe_dropout(h)
        # Constrain the OUTPUT as well: it becomes the scan carry (= the
        # remat residual that lives for the whole backward pass) — without
        # this GSPMD may keep loop carries model-replicated.
        return self._shard(self._ffn_block(x), cfg.activation_partition)

    # decode interface — state is the mixer's (opaque) state
    @no_context
    def state_partition_specs(self, *_):
        return self.self_attention.state_partition_specs()

    def init_states(self, batch_size: int, max_len: int):
        return self.self_attention.init_states(batch_size, max_len)

    def prefill(self, state, x, positions=None, length=None):
        cfg = self.config
        x = self._to_compute(x)
        x = self._shard(x, cfg.activation_partition)
        state, h = self.self_attention.prefill(
            state, self.attn_norm(x), positions=positions, length=length)
        if cfg.use_post_attention_norm:
            h = self.post_attn_norm(h)
        x = x + h
        return state, self._ffn_block(x)

    def extend_step(self, state, x_step):
        cfg = self.config
        x_step = self._to_compute(x_step)
        state, h = self.self_attention.extend_step(state, self.attn_norm(x_step))
        if cfg.use_post_attention_norm:
            h = self.post_attn_norm(h)
        x = x_step + h
        return state, self._ffn_block(x)


class Block(BaseLayer):
    """A fixed heterogeneous sequence of layers (e.g. jamba's 7×mamba + 1×attn
    super-block, or gemma2's (local, global) pair). Blocks are the unit that
    ``Repeat`` scans over."""

    @config_class
    class Config(BaseLayer.Config):
        layers: Required[List[ConfigBase]] = REQUIRED
        # Nested remat: checkpoint each layer individually so the block's
        # backward recomputes ONE layer's working set at a time instead of
        # holding all of them live (crucial for 8-layer jamba super-blocks).
        remat_each_layer: bool = False

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        self._layer_names = []
        for i, layer_cfg in enumerate(cfg.layers):
            name = f"layer{i}"
            layer_cfg = layer_cfg.clone()
            if "dtype_policy" in layer_cfg.keys():
                maybe_set(layer_cfg, dtype_policy=cfg.dtype_policy)
            self._add_child(name, layer_cfg)
            self._layer_names.append(name)

    def forward(self, x, positions=None):
        ctx = self._ctx
        nested = self.config.remat_each_layer and ctx.is_training
        for name in self._layer_names:
            child = getattr(self, name)
            if not nested:
                x = child(x, positions=positions)
                continue
            key = None
            if ctx.prng_key is not None:
                import zlib

                key = jax.random.fold_in(
                    ctx.prng_key, zlib.crc32(name.encode()))

            def fn(params, x, child=child, key=key):
                out, col = functional(
                    child, state=params, inputs={"x": x, "positions": positions},
                    prng_key=key, is_training=True)
                return out, (col.summaries, col.module_outputs)

            x, (summaries, module_outputs) = jax.checkpoint(
                fn, prevent_cse=False)(ctx.state.get(name, {}), x)
            for k, v in summaries.items():
                ctx.add_summary(f"{name}/{k}", v)
            for k, v in module_outputs.items():
                ctx.add_module_output(f"{name}/{k}", v)
        return x

    @no_context
    def state_partition_specs(self, *_):
        return {n: getattr(self, n).state_partition_specs()
                for n in self._layer_names}

    def init_states(self, batch_size: int, max_len: int):
        return {n: getattr(self, n).init_states(batch_size, max_len)
                for n in self._layer_names}

    def prefill(self, state, x, positions=None, length=None):
        new_state = {}
        for n in self._layer_names:
            new_state[n], x = getattr(self, n).prefill(
                state[n], x, positions=positions, length=length)
        return new_state, x

    def extend_step(self, state, x_step):
        new_state = {}
        for n in self._layer_names:
            new_state[n], x_step = getattr(self, n).extend_step(state[n], x_step)
        return new_state, x_step


def _stack_spec(spec: ParameterSpec, num: int) -> ParameterSpec:
    axes = spec.mesh_axes
    new_axes = (None,) + tuple(axes) if axes is not None else None
    return ParameterSpec(
        shape=(num,) + tuple(spec.shape),
        dtype=spec.dtype,
        initializer=spec.initializer,
        mesh_axes=new_axes,
        weight_decay_scale=spec.weight_decay_scale,
    )


class Repeat(BaseLayer):
    """num_layers × layer, parameters stacked on a leading axis, lax.scan'd.

    Side outputs emitted by inner layers (summaries, MoE aux losses) are
    collected per-iteration by the scan and re-emitted stacked — ancestors
    remain oblivious, preserving encapsulation through the scan boundary.
    """

    @config_class
    class Config(BaseLayer.Config):
        layer: Required[ConfigBase] = REQUIRED
        num_layers: Required[int] = REQUIRED
        # None = no remat; otherwise a policy spec string resolved by
        # repro.trainer.remat.policy_from_spec (e.g. "full",
        # "save:attn_out,ffn_out", "offload:ffn_hidden").
        remat_policy: Optional[str] = "full"
        # Reversible two-stream residual stack (repro.memopt.reversible):
        # the backward pass reconstructs activations from the layers'
        # invertible structure instead of saving them — O(1) activation
        # memory in depth, superseding remat_policy inside this stack.
        # Requires an invertible inner layer (attn_branch/ffn_branch, zero
        # residual dropout); training-side only (decode paths raise).
        reversible: bool = False

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        layer = cfg.layer.clone()
        if "dtype_policy" in layer.keys():
            maybe_set(layer, dtype_policy=self.config.dtype_policy)
        self._add_child("layer", layer)
        if cfg.reversible:
            from repro.memopt.reversible import validate_reversible

            validate_reversible(self.layer)  # fail at build, not in-step

    # --- stacked params ------------------------------------------------------

    def create_parameter_specs_recursively(self):
        inner = self.layer.create_parameter_specs_recursively()
        L = self.config.num_layers
        return {"layer": jax.tree.map(
            lambda s: _stack_spec(s, L), inner,
            is_leaf=lambda s: isinstance(s, ParameterSpec))}

    def initialize_parameters_recursively(self, prng_key):
        L = self.config.num_layers
        keys = jax.random.split(prng_key, L)
        init = jax.vmap(self.layer.initialize_parameters_recursively)
        return {"layer": init(keys)}

    # --- scan plumbing ---------------------------------------------------------

    def _scan(self, fn_name: str, carry_x, *, per_layer_state=None,
              positions=None, length=None):
        """Runs ``layer.<fn_name>`` over stacked params via lax.scan.

        carry: activations; xs: (params_i[, state_i][, key_i]);
        ys: (side outputs[, new_state_i]).
        """
        cfg = self.config
        ctx = self._ctx
        params = self.state["layer"]
        L = cfg.num_layers
        keys = None
        if ctx.prng_key is not None:
            keys = jax.random.split(ctx.prng_key, L)
        is_training = ctx.is_training

        def body(x, xs):
            params_i = xs["params"]
            key_i = xs.get("key")
            if fn_name == "forward":
                inputs = {"x": x}
            elif fn_name == "prefill":
                inputs = {"state": xs["state"], "x": x}
            else:  # extend_step
                inputs = {"state": xs["state"], "x_step": x}
            if positions is not None and fn_name in ("forward", "prefill"):
                inputs["positions"] = positions
            if length is not None and fn_name == "prefill":
                inputs["length"] = length
            out, collection = functional(
                self.layer,
                state=params_i,
                inputs=inputs,
                prng_key=key_i,
                is_training=is_training,
                method=fn_name,
            )
            side = {
                "summaries": collection.summaries,
                "module_outputs": collection.module_outputs,
                "state_updates": collection.state_updates,
            }
            if fn_name == "forward":
                return out, side
            new_state, y = out
            return y, {"side": side, "state": new_state}

        if cfg.remat_policy is not None and is_training and fn_name == "forward":
            from repro.trainer.remat import policy_from_spec

            policy = policy_from_spec(cfg.remat_policy)
            # The scan keeps XLA from merging the recomputation with the
            # forward pass (CSE), except where XLA removes a one-trip loop.
            body = jax.checkpoint(body, policy=policy,
                                  prevent_cse=cfg.num_layers == 1)

        xs: Dict[str, Any] = {"params": params}
        if keys is not None:
            xs["key"] = keys
        if per_layer_state is not None:
            xs["state"] = per_layer_state
        return jax.lax.scan(body, carry_x, xs)

    # --- public interface -------------------------------------------------------

    def forward(self, x, positions=None):
        if self.config.reversible:
            from repro.memopt.reversible import reversible_forward

            # Side outputs from inner layers are dropped here (documented
            # in repro.memopt.reversible): the custom_vjp boundary cannot
            # re-emit per-layer collections.
            return reversible_forward(self, x, positions=positions)
        y, side = self._scan("forward", x, positions=positions)
        self._reemit(side)
        return y

    def _check_not_reversible(self, method: str):
        if self.config.reversible:
            raise NotImplementedError(
                f"Repeat.{method} is not available on a reversible stack: "
                "reversible=True is a training/scoring-memory knob "
                "(forward-only); the incremental decode interface has no "
                "two-stream layout. Export/serve such models through "
                "forward(), or train with reversible=False when the "
                "checkpoint must serve through prefill/extend_step.")

    @no_context
    def state_partition_specs(self, *_):
        inner = self.layer.state_partition_specs()

        def rec(node):
            if isinstance(node, dict):
                return {k: rec(v) for k, v in node.items()}
            if node is None:
                return None
            return (None,) + tuple(node)  # stacked layer axis

        return rec(inner)

    def init_states(self, batch_size: int, max_len: int):
        self._check_not_reversible("init_states")
        proto, _ = functional(
            self.layer, state={}, inputs=(batch_size, max_len),
            is_training=False, method="init_states")
        L = self.config.num_layers
        return jax.tree.map(lambda a: jnp.stack([a] * L, axis=0)
                            if hasattr(a, "shape") else a, proto)

    def prefill(self, state, x, positions=None, length=None):
        self._check_not_reversible("prefill")
        y, ys = self._scan("prefill", x, per_layer_state=state,
                           positions=positions, length=length)
        self._reemit(ys["side"])
        return ys["state"], y

    def extend_step(self, state, x_step):
        self._check_not_reversible("extend_step")
        y, ys = self._scan("extend_step", x_step, per_layer_state=state)
        self._reemit(ys["side"])
        return ys["state"], y

    def _reemit(self, side: Dict[str, Dict[str, Any]]):
        """Re-emit per-layer (stacked) side outputs into the parent collection."""
        for key, value in side["summaries"].items():
            self._ctx.add_summary(f"stack/{key}", value)
        for key, value in side["module_outputs"].items():
            self._ctx.add_module_output(f"stack/{key}", value)
        # State updates (e.g. fp8 amax histories) re-emit under "layer/":
        # the scan stacks each update (L, ...), which is exactly the layout
        # of the stacked params under this Repeat's "layer" subtree, so the
        # trainer's fold-back addresses them without knowing about scan.
        for key, value in side.get("state_updates", {}).items():
            self._ctx.add_state_update(f"layer/{key}", value)


class StackedTransformer(BaseLayer):
    """Python-loop stack (unscanned) — used for small models and as the
    readability baseline; shares the Repeat interface."""

    @config_class
    class Config(BaseLayer.Config):
        layers: Required[List[ConfigBase]] = REQUIRED

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        self._names = []
        for i, c in enumerate(cfg.layers):
            n = f"layer{i}"
            c = c.clone()
            if "dtype_policy" in c.keys():
                maybe_set(c, dtype_policy=cfg.dtype_policy)
            self._add_child(n, c)
            self._names.append(n)

    def forward(self, x, positions=None):
        for n in self._names:
            x = getattr(self, n)(x, positions=positions)
        return x

    @no_context
    def state_partition_specs(self, *_):
        return {n: getattr(self, n).state_partition_specs() for n in self._names}

    def init_states(self, batch_size, max_len):
        return {n: getattr(self, n).init_states(batch_size, max_len) for n in self._names}

    def prefill(self, state, x, positions=None, length=None):
        out = {}
        for n in self._names:
            out[n], x = getattr(self, n).prefill(
                state[n], x, positions=positions, length=length)
        return out, x

    def extend_step(self, state, x_step):
        out = {}
        for n in self._names:
            out[n], x_step = getattr(self, n).extend_step(state[n], x_step)
        return out, x_step


class Decoder(BaseLayer):
    """Embedding -> stack -> final norm -> LM head (tied by default)."""

    @config_class
    class Config(BaseLayer.Config):
        vocab_size: Required[int] = REQUIRED
        dim: Required[int] = REQUIRED
        emb: ConfigBase = Embedding.Config()
        stack: Required[ConfigBase] = REQUIRED
        final_norm: ConfigBase = RMSNorm.Config()
        # None -> weight tying via emb.attend().
        lm_head: Optional[ConfigBase] = None
        logits_softcap: Optional[float] = None
        emb_dropout: float = 0.0
        # Compute dtype for the stack (bf16 = production mixed precision).
        activation_dtype: Any = jnp.float32
        logits_partition: PartitionSpecLike = (("pod", "data"), None, "model")

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config
        self._add_child("emb", maybe_set(cfg.emb.clone(
            num_embeddings=cfg.vocab_size, dim=cfg.dim),
            dtype_policy=cfg.dtype_policy))
        self._add_child("stack", maybe_set(cfg.stack.clone(),
                                           dtype_policy=cfg.dtype_policy))
        fn = cfg.final_norm.clone()
        if "input_dim" in fn.keys() and not fn.input_dim:
            fn.set(input_dim=cfg.dim)
        maybe_set(fn, dtype_policy=cfg.dtype_policy)
        self._add_child("final_norm", fn)
        if cfg.lm_head is not None:
            self._add_child("lm_head", maybe_set(cfg.lm_head.clone(
                input_dim=cfg.dim, output_dim=cfg.vocab_size, bias=False),
                dtype_policy=cfg.dtype_policy))
        if cfg.emb_dropout:
            self._add_child("dropout", Dropout.default_config().set(rate=cfg.emb_dropout))

    def _embed(self, input_ids, input_embeddings):
        if input_embeddings is None:
            x = self.emb(input_ids)
        elif input_ids is None:
            x = input_embeddings
        else:
            # Multimodal prefix layout: media embeddings occupy positions
            # [0, P); text tokens fill the rest (phi-3-vision stub frontend).
            P = input_embeddings.shape[1]
            text = self.emb(input_ids)
            x = jnp.concatenate([input_embeddings.astype(text.dtype), text[:, P:]], axis=1)
        if self.config.emb_dropout:
            x = self.dropout(x)
        # The dtype policy (when set) wins over the legacy activation_dtype
        # field: the stack runs entirely in the policy compute dtype.
        if self.compute_dtype is not None:
            return x.astype(self.compute_dtype)
        return x.astype(self.config.activation_dtype)

    def _head(self, h):
        cfg = self.config
        h = self.final_norm(h)
        if cfg.lm_head is not None:
            logits = self.lm_head(h)
        else:
            logits = self.emb.attend(h)
        if cfg.logits_softcap:
            logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
        return self._shard(self._to_output(logits), cfg.logits_partition)

    def forward(self, input_ids=None, *, input_embeddings=None, positions=None):
        return self.head(self.hidden(
            input_ids, input_embeddings=input_embeddings, positions=positions))

    def hidden(self, input_ids=None, *, input_embeddings=None, positions=None):
        """Final-layer hidden states (pre-norm/head) — lets the model compute
        chunked losses without materializing full logits."""
        x = self._embed(input_ids, input_embeddings)
        if positions is None:
            positions = jnp.arange(x.shape[1])
        return self.stack(x, positions=positions)

    def head(self, h):
        return self._head(h)

    @no_context
    def state_partition_specs(self, *_):
        return self.stack.state_partition_specs()

    def init_states(self, batch_size: int, max_len: int):
        return self.stack.init_states(batch_size, max_len)

    def prefill(self, state, input_ids=None, *, input_embeddings=None,
                positions=None, length=None):
        x = self._embed(input_ids, input_embeddings)
        if positions is None:
            positions = jnp.arange(x.shape[1])
        state, h = self.stack.prefill(state, x, positions=positions, length=length)
        return state, self._head(h)

    def extend_step(self, state, ids_step):
        x = self.emb(ids_step)
        state, h = self.stack.extend_step(state, x)
        return state, self._head(h)
