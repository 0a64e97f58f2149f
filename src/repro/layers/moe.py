"""Mixture-of-Experts FFN — the paper's flagship drop-in replacement (§2.1).

Two expert layers, chosen by the model's configuration:

* :class:`MoELayer` (mixtral, arctic, jamba): GShard softmax routing with a
  capacity, einsum dispatch/combine; tokens over capacity are dropped.
* :class:`DroplessMoE` (DeepSeek-V3 / Moonlight): sigmoid routing with a
  selection bias, no capacity, shared experts, and a layer told which
  experts it holds: it routes over all of them and computes only its own
  experts' part, through a grouped matrix product.

MoELayer is GSPMD/GShard-style capacity-based token-choice top-k routing with einsum
dispatch/combine, designed for expert parallelism over the "model" (or a
dedicated "expert") mesh axis. The load-balance and router-z auxiliary
losses are emitted through the InvocationContext (``add_module_output``),
so NO ancestor layer — TransformerLayer, Repeat, Decoder, CausalLM — knows
MoE exists. That is precisely the encapsulation property the paper measures
with LoC-complexity.

Interface-compatible with FeedForward: forward(x: (B,S,D)) -> (B,S,D).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.config import (
    REQUIRED,
    FunctionConfigBase,
    Required,
    config_class,
    maybe_set,
)
from repro.core.utils import PartitionSpecLike, remat_name
from repro.kernels import ops as kernel_ops
from repro.kernels.gmm import tiling_for
from repro.layers.base import (BaseLayer, KernelConfig, ParameterSpec,
                               fan_in_init, normal_init, zeros_init)
from repro.layers.basic import get_activation
from repro.layers.ffn import FeedForward

__all__ = ["DroplessMoE", "MoELayer", "ResidualMoE", "SigmoidRouter",
           "TopKRouter"]


class TopKRouter(BaseLayer):
    """Token-choice top-k router with capacity-aware position assignment.

    Returns (dispatch (G,S,E,C) bool-ish, combine (G,S,E,C) float) tensors.
    Encapsulates: gating nonlinearity, top-k normalization, capacity logic,
    aux losses. Swappable for other routing strategies by config.
    """

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        num_experts: Required[int] = REQUIRED
        top_k: int = 2
        capacity_factor: float = 2.0
        # mixtral renormalizes the top-k gate weights to sum to 1.
        normalize_top_k: bool = True
        load_balance_weight: float = 0.01
        router_z_weight: float = 0.001
        gate_weight_partition: PartitionSpecLike = ("data", None)
        # (G, S, E, C) dispatch/combine sharding — set by the parent MoELayer
        # so the fp32 routing tensors are expert-sharded from birth.
        dispatch_partition: PartitionSpecLike = (("pod", "data"), None, "model", None)

    def _create_layer_parameter_specs(self):
        cfg = self.config
        return {
            "gate": ParameterSpec(
                shape=(cfg.input_dim, cfg.num_experts),
                dtype=cfg.param_dtype,
                initializer=normal_init(0.02),
                mesh_axes=cfg.gate_weight_partition,
            )
        }

    def forward(self, x: jax.Array, *, capacity: int) -> Tuple[jax.Array, jax.Array]:
        cfg = self.config
        G, S, D = x.shape
        E, K, C = cfg.num_experts, cfg.top_k, capacity
        logits = (x.astype(jnp.float32) @ self.state["gate"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)  # (G,S,E)

        top_vals, top_idx = jax.lax.top_k(probs, K)  # (G,S,K)
        if cfg.normalize_top_k:
            top_vals = top_vals / jnp.maximum(
                jnp.sum(top_vals, -1, keepdims=True), 1e-9)

        dp = tuple(cfg.dispatch_partition) if cfg.dispatch_partition else (None,) * 4
        gse = (dp[0], dp[1], dp[2])

        # Sequential capacity assignment: all k=0 choices first (GShard).
        dispatch = jnp.zeros((G, S, E, C), jnp.float32)
        combine = jnp.zeros((G, S, E, C), jnp.float32)
        counts = jnp.zeros((G, E), jnp.float32)  # tokens already at each expert
        frac_dispatched_first = None
        for k in range(K):
            mask_k = jax.nn.one_hot(top_idx[..., k], E, dtype=jnp.float32)  # (G,S,E)
            mask_k = self._shard(mask_k, gse)
            pos_k = jnp.cumsum(mask_k, axis=1) - 1.0 + counts[:, None, :]
            keep_k = (pos_k < C) * mask_k  # (G,S,E)
            counts = counts + jnp.sum(keep_k, axis=1)
            oh_pos = jax.nn.one_hot(pos_k.astype(jnp.int32), C, dtype=jnp.float32)
            oh_pos = self._shard(oh_pos, dp)
            disp_k = keep_k[..., None] * oh_pos  # (G,S,E,C)
            dispatch = self._shard(dispatch + disp_k, dp)
            combine = self._shard(
                combine + disp_k * top_vals[..., k][..., None, None], dp)
            if k == 0:
                frac_dispatched_first = jnp.mean(mask_k, axis=(0, 1))  # (E,)

        # --- aux losses, emitted without ancestor knowledge ------------------
        mean_prob = jnp.mean(probs, axis=(0, 1))  # (E,)
        load_balance = E * jnp.sum(frac_dispatched_first * mean_prob)
        z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        aux = cfg.load_balance_weight * load_balance + cfg.router_z_weight * z_loss
        self.add_module_output("aux_loss", aux)
        self.add_summary("load_balance_loss", load_balance)
        self.add_summary("router_z_loss", z_loss)
        self.add_summary("expert_load_max", jnp.max(frac_dispatched_first) * E)
        dispatched_frac = jnp.sum(dispatch) / (G * S * K)
        self.add_summary("dispatched_fraction", dispatched_frac)  # 1 - drop rate
        return dispatch, combine


class MoELayer(BaseLayer):
    """Drop-in FFN replacement. Expert weights (E, D, H) shard E over the
    expert axis when divisible (expert parallelism); the dispatch einsums
    become all-to-alls under GSPMD."""

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        hidden_dim: Required[Union[int, FunctionConfigBase]] = REQUIRED
        num_experts: Required[int] = REQUIRED
        top_k: int = 2
        capacity_factor: float = 2.0
        # GShard grouping: tokens are routed in groups of this size, bounding
        # the (G, g, E, C) dispatch tensors to O(tokens * g) instead of
        # O(tokens * S) when sequences are long (32k prefill!). None = one
        # group per sequence (legacy behaviour).
        group_size: Optional[int] = None
        activation: Union[str, Tuple[str, ...]] = ("linear", "nn.silu")
        router: TopKRouter.Config = TopKRouter.Config()
        # (E, D, H): shard experts over "expert"/"model" when divisible; the
        # config builders choose (see configs/common.py).
        up_weight_partition: PartitionSpecLike = ("model", "data", None)
        down_weight_partition: PartitionSpecLike = ("model", None, "data")
        # (G, S, E, C) dispatch activations.
        dispatch_partition: PartitionSpecLike = (("pod", "data"), None, "model", None)
        # (E, G, C, D) expert-major activations.
        expert_partition: PartitionSpecLike = ("model", ("pod", "data"), None, None)

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config
        hidden = cfg.hidden_dim
        if isinstance(hidden, FunctionConfigBase):
            cfg.set(hidden_dim=hidden.instantiate()(cfg.input_dim))
        router = cfg.router.clone()
        router.set(input_dim=cfg.input_dim, num_experts=cfg.num_experts,
                   top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                   dispatch_partition=cfg.dispatch_partition)
        maybe_set(router, dtype_policy=cfg.dtype_policy)
        self._add_child("router", router)

    def _create_layer_parameter_specs(self):
        cfg = self.config
        E, D, H = cfg.num_experts, cfg.input_dim, cfg.hidden_dim
        acts = cfg.activation if isinstance(cfg.activation, (tuple, list)) else (cfg.activation,)
        specs = {}
        for i in range(len(acts)):
            name = f"wi_{i}" if len(acts) > 1 else "wi"
            specs[name] = ParameterSpec(
                shape=(E, D, H), dtype=cfg.param_dtype,
                initializer=fan_in_init(fan_in_axes=(-2,)),
                mesh_axes=cfg.up_weight_partition)
        specs["wo"] = ParameterSpec(
            shape=(E, H, D), dtype=cfg.param_dtype,
            initializer=fan_in_init(fan_in_axes=(-2,)),
            mesh_axes=cfg.down_weight_partition)
        return specs

    def _capacity(self, S: int) -> int:
        cfg = self.config
        per_expert = (S * cfg.top_k) / cfg.num_experts
        return max(4, int(per_expert * cfg.capacity_factor + 0.5))

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        # Boundary cast: expert matmuls run in the compute dtype; the router
        # keeps its fp32 gating/aux-loss island.
        x = self._to_compute(x)
        B0, S0, D = x.shape
        g = cfg.group_size
        if g and S0 > g and S0 % g == 0:
            x = x.reshape(B0 * (S0 // g), g, D)
        B, S, D = x.shape
        C = self._capacity(S)
        acts = cfg.activation if isinstance(cfg.activation, (tuple, list)) else (cfg.activation,)

        dispatch, combine = self.router(x, capacity=C)
        dispatch = self._shard(dispatch.astype(jnp.bfloat16), cfg.dispatch_partition)
        combine = self._shard(combine.astype(x.dtype), cfg.dispatch_partition)
        dispatch = remat_name(dispatch, "moe_dispatch")

        # Dispatch tokens to experts: (E, G, C, D). Under expert parallelism
        # this einsum lowers to an all-to-all over the expert axis.
        xe = jnp.einsum("gsec,gsd->egcd", dispatch.astype(x.dtype), x)
        xe = self._shard(xe, cfg.expert_partition)

        # Per-expert FFN (optionally gated).
        if len(acts) == 1:
            h = get_activation(acts[0])(
                jnp.einsum("egcd,edh->egch", xe, self.state["wi"].astype(x.dtype)))
        else:
            h = None
            for i, name in enumerate(acts):
                w = self.state[f"wi_{i}"].astype(x.dtype)
                a = get_activation(name)(jnp.einsum("egcd,edh->egch", xe, w))
                h = a if h is None else h * a
        ye = jnp.einsum("egch,ehd->egcd", h, self.state["wo"].astype(x.dtype))
        ye = self._shard(ye, cfg.expert_partition)

        # Combine back to token order.
        y = jnp.einsum("gsec,egcd->gsd", combine, ye)
        if y.shape[0] != B0:
            y = y.reshape(B0, S0, D)
        return remat_name(y, "ffn_out")


class ResidualMoE(BaseLayer):
    """Arctic-style: a small dense FFN in parallel with the MoE FFN.

    Pure composition: both children keep their own encapsulated configs.
    """

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        dense: FeedForward.Config = FeedForward.Config()
        moe: MoELayer.Config = MoELayer.Config()

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        dense = cfg.dense.clone()
        moe = cfg.moe.clone()
        for c in (dense, moe):
            if not c.input_dim:
                c.set(input_dim=cfg.input_dim)
            maybe_set(c, dtype_policy=cfg.dtype_policy)
        self._add_child("dense", dense)
        self._add_child("moe", moe)

    def forward(self, x: jax.Array) -> jax.Array:
        return self.dense(x) + self.moe(x)


class SigmoidRouter(BaseLayer):
    """DeepSeek-V3 routing with one expert group (``noaux_tc``,
    ``n_group = topk_group = 1``) [arXiv:2412.19437, §2.1.2]:

      s     = sigmoid(x W)                      over every expert, fp32
      top   = top_k(s + b)                      b: selection only
      gates = s[top] / sum(s[top]) * routed_scale

    ``b`` (the correction bias) is a parameter that takes no gradient: after
    each training step it moves by ``bias_rate * sign(mean load - load)``,
    emitted as a state update that the train step folds back into the
    params. ``balance_weight`` (alpha) weighs the sequence-wise balance
    loss: alpha * sum_i f_i P_i per sequence, f_i = E / (K S) * (selections
    of expert i), P_i = mean over the sequence of s_i / sum_j s_j; emitted
    as an ``aux_loss`` and averaged over sequences.
    """

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        num_experts: Required[int] = REQUIRED
        top_k: Required[int] = REQUIRED
        routed_scale: float = 1.0
        balance_weight: float = 0.0
        bias_rate: float = 0.0

    def _create_layer_parameter_specs(self):
        cfg = self.config
        return {
            "weight": ParameterSpec(
                shape=(cfg.input_dim, cfg.num_experts), dtype=jnp.float32,
                initializer=fan_in_init(), mesh_axes=(None, None)),
            "bias": ParameterSpec(
                shape=(cfg.num_experts,), dtype=jnp.float32,
                initializer=zeros_init(), mesh_axes=(None,),
                weight_decay_scale=0.0),
        }

    def forward(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(B, S, D) -> (experts (B, S, K) int32, gates (B, S, K) fp32)."""
        cfg = self.config
        E, K = cfg.num_experts, cfg.top_k
        scores = jax.nn.sigmoid(jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32), self.state["weight"],
            precision=jax.lax.Precision.HIGHEST))
        bias = jax.lax.stop_gradient(self.state["bias"])
        _, experts = jax.lax.top_k(scores + bias, K)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        gates = chosen / jnp.sum(chosen, -1, keepdims=True) * cfg.routed_scale
        # (B, E) selections per sequence.
        counts = jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32),
                         axis=(1, 2))
        if cfg.balance_weight:
            S = x.shape[1]
            f = counts * (E / (K * S))
            p = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
            self.add_module_output(
                "aux_loss", cfg.balance_weight * jnp.mean(jnp.sum(f * p, -1)))
        if cfg.bias_rate and self.is_training:
            load = jnp.sum(counts, axis=0)
            self.add_state_update("bias", self.state["bias"] + cfg.bias_rate
                                  * jnp.sign(jnp.mean(load) - load))
        return experts, gates


# Chunk slots over the expected held rows of a sequence (DroplessMoE).
_CHUNKS_PER_HELD = 6


def _over_chunks(chunk: int, total: jax.Array, n: int, body, carry):
    """``carry = body(carry, start)`` for each slot ``[start, start +
    chunk)`` of ``n`` sorted rows that begins before ``total``; the other
    slots run nothing. A scan of ``cond``s, not a ``vmap`` (which would run
    both branches)."""
    def slot(carry, start):
        return jax.lax.cond(start < total, body, lambda c, _: c, carry,
                            start), None

    return jax.lax.scan(slot, carry, jnp.arange(0, n, chunk))[0]


def _chunk_of(start, chunk, total, order, top_k):
    """The sorted rows ``[start, start + chunk)``: their assignments (flat
    ``(token, k)`` indices), their tokens, and which lie before ``total``
    (as a column)."""
    pos = jax.lax.dynamic_slice_in_dim(order, start, chunk)
    live = (start + jnp.arange(chunk)) < total
    return pos, pos // top_k, live[:, None]


def _rows(a, start, chunk):
    return jax.lax.dynamic_slice_in_dim(a, start, chunk)


def _put(buf, rows, start):
    return jax.lax.dynamic_update_slice_in_dim(buf, rows.astype(buf.dtype),
                                               start, 0)


def _scatter_add(parts, order, total, top_k, chunk, gates=None):
    """(S, D) f32: each sorted row before ``total`` of the sum of the
    ``parts`` buffers (times its assignment's gate), added at its token."""
    def body(acc, start):
        pos, token, live = _chunk_of(start, chunk, total, order, top_k)
        rows = _rows(parts[0], start, chunk).astype(jnp.float32)
        for part in parts[1:]:
            rows = rows + _rows(part, start, chunk).astype(jnp.float32)
        if gates is not None:
            rows = rows * gates[pos][:, None]
        return acc.at[token].add(jnp.where(live, rows, 0))

    n, d = parts[0].shape
    return _over_chunks(chunk, total, n, body,
                        jnp.zeros((n // top_k, d), jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(x, order, total, top_k, chunk):
    """x (S, D) -> the (S*K, D) sorted rows, twice: row i is ``x[order[i] //
    K]`` before ``total`` and 0 after, gathered only in the chunks that
    begin before ``total``. One copy for each of the two grouped products
    that read it, so that their gradients are added chunk by chunk in the
    backward (a scatter-add of the same chunks), not over the whole
    buffer."""
    def body(buf, start):
        _, token, live = _chunk_of(start, chunk, total, order, top_k)
        return _put(buf, jnp.where(live, x[token], 0), start)

    rows = _over_chunks(chunk, total, order.shape[0], body,
                        jnp.zeros((order.shape[0], x.shape[-1]), x.dtype))
    return rows, rows


def _dispatch_fwd(x, order, total, top_k, chunk):
    return _dispatch(x, order, total, top_k, chunk), (order, total)


def _dispatch_bwd(top_k, chunk, res, d_rows):
    order, total = res
    dx = _scatter_add(d_rows, order, total, top_k, chunk)
    return dx.astype(d_rows[0].dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine(out, gates, order, total, top_k, chunk):
    """(S*K, D) sorted expert outputs and (S*K,) f32 gates in assignment
    order -> (S, D) f32: each sorted row before ``total`` times its gate,
    added at its token, chunk by chunk. The gradient gathers the same
    chunks: no (S, K, D) copy in either direction."""
    return _scatter_add((out,), order, total, top_k, chunk, gates)


def _combine_fwd(out, gates, order, total, top_k, chunk):
    return (_combine(out, gates, order, total, top_k, chunk),
            (out, gates, order, total))


def _combine_bwd(top_k, chunk, res, dy):
    out, gates, order, total = res

    def body(carry, start):
        d_out, d_gates = carry
        pos, token, live = _chunk_of(start, chunk, total, order, top_k)
        dy_rows = dy[token]
        d_out = _put(d_out, jnp.where(live, dy_rows * gates[pos][:, None], 0),
                     start)
        d_g = jnp.sum(_rows(out, start, chunk).astype(jnp.float32) * dy_rows,
                      axis=-1)
        d_gates = d_gates.at[pos].set(jnp.where(live[:, 0], d_g, 0),
                                      unique_indices=True)
        return d_out, d_gates

    d_out, d_gates = _over_chunks(chunk, total, order.shape[0], body,
                                  (jnp.zeros_like(out), jnp.zeros_like(gates)))
    return d_out, d_gates, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


class DroplessMoE(BaseLayer):
    """Drop-in FFN: routed experts without capacity, plus shared experts.

    The layer holds experts ``[first, first + count)`` of ``num_experts``
    (``held_experts``; None holds them all). It routes every token over all
    experts and computes only its own experts' part of the result: on a
    chip of an expert-parallel group, that part is what this chip adds, and
    the absent experts' parts would come from the other chips. No token is
    dropped. Each sequence's held assignments are sorted by expert and run
    through the grouped matrix product (``moe.gmm``), one sequence at a
    time so that one sequence's sorted rows are live at once:

      y = sum over the held top-k of gate_i * FFN_i(x) + FFN_shared(x)

    Only the held rows are moved. The stable sort puts a sequence's held
    assignments first among its ``S * K`` sorted rows; the dispatch (which
    gathers token rows into expert order) and the combine (which scales
    each row by its gate, in f32, and adds it at its token) work on chunks
    of ``C`` sorted rows, a ``cond`` per chunk slot in a ``scan``, and run
    the slots that begin before the sequence's held total, which is read on
    the device: about ``ceil(held / C)`` of the ``S * K / C``. Their
    gradients are the same loops the other way round (a scatter-add of the
    chunks for the dispatch, a gather for the combine). ``C`` follows from
    shapes: the largest multiple of the grouped products' row tile that
    divides ``S * K`` and is at most the expected held rows ``S * K * count
    / num_experts`` over 6 (1,024 of 49,152 in the Moonlight cell), so a
    sequence wastes at most ``C - 1`` rows. A sequence whose assignments
    are all held runs every slot. The grouped products are not chunked:
    one call per weight over the whole ``(S * K, .)`` buffer, whose grid
    already covers only the held rows' tiles.

    Scopes: ``router`` (the router module), ``moe.dispatch`` (sort and
    gather into expert order), ``expert_gmm`` (the grouped products, forward
    and backward), ``moe.combine`` (the weighted sum back in token order).
    Summaries: ``held_assignments`` (rows the held experts computed),
    ``held_load_max_over_mean`` (over the held experts) and
    ``held_chunk_share`` (chunk slots run over slots, the sequences' mean).
    """

    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        hidden_dim: Required[int] = REQUIRED
        num_experts: Required[int] = REQUIRED
        top_k: Required[int] = REQUIRED
        # (first, count) of the experts this layer holds; None: all.
        held_experts: Optional[Tuple[int, int]] = None
        router: SigmoidRouter.Config = SigmoidRouter.Config()
        # The shared experts as one gated FFN (None: no shared expert).
        shared: Optional[FeedForward.Config] = None
        kernel: KernelConfig = KernelConfig()

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config
        if cfg.held_experts is None:
            cfg.set(held_experts=(0, cfg.num_experts))
        first, count = cfg.held_experts
        if not 0 <= first < first + count <= cfg.num_experts:
            raise ValueError(f"held_experts {cfg.held_experts} outside "
                             f"{cfg.num_experts} experts")
        router = cfg.router.clone().set(
            input_dim=cfg.input_dim, num_experts=cfg.num_experts,
            top_k=cfg.top_k)
        self._add_child("router", maybe_set(router,
                                            dtype_policy=cfg.dtype_policy))
        if cfg.shared is not None:
            self._add_child("shared_experts", maybe_set(
                cfg.shared.clone().set(input_dim=cfg.input_dim),
                dtype_policy=cfg.dtype_policy))

    # SwiGLU experts: wi_0 the linear branch, wi_1 the SiLU branch, wo down.
    _WEIGHTS = ("wi_0", "wi_1", "wo")

    def _create_layer_parameter_specs(self):
        cfg = self.config
        n, D, H = cfg.held_experts[1], cfg.input_dim, cfg.hidden_dim
        up = ((n, D, H), (None, None, "model"))
        down = ((n, H, D), (None, "model", None))
        return {name: ParameterSpec(
            shape=shape, dtype=cfg.param_dtype,
            initializer=fan_in_init(fan_in_axes=(-2,)), mesh_axes=axes)
            for name, (shape, axes) in zip(self._WEIGHTS, (up, up, down))}

    def _chunk_rows(self, S: int) -> int:
        """Rows in one dispatch/combine chunk of a sequence of ``S`` tokens:
        the largest multiple of the grouped products' row tile that divides
        ``S * K`` and is at most the expected held rows, ``S * K * count /
        num_experts``, over ``_CHUNKS_PER_HELD``; at least the tile."""
        cfg = self.config
        n = S * cfg.top_k
        tile = tiling_for(n, cfg.input_dim, cfg.hidden_dim)[0]
        want = n * cfg.held_experts[1] // cfg.num_experts // _CHUNKS_PER_HELD
        return max([c for c in range(tile, want + 1, tile) if n % c == 0],
                   default=tile)

    def _sequence(self, weights, x, local, gates):
        """One sequence's routed part: x (S, D), local (S, K) held expert
        index or ``count`` (not held), gates (S, K). Returns (y (S, D) fp32,
        rows per held expert (count,) int32)."""
        cfg = self.config
        count = cfg.held_experts[1]
        S, K = local.shape
        chunk = self._chunk_rows(S)
        with jax.named_scope("moe.dispatch"):
            flat = local.reshape(-1)
            # Stable: held experts' rows in expert order, then the rest.
            order = jnp.argsort(flat, stable=True)
            # Counted by comparison: a bincount is a serial scatter-add.
            sizes = jnp.sum(flat[:, None] == jnp.arange(count), axis=0,
                            dtype=jnp.int32)
            total = jnp.sum(sizes)
            rows_0, rows_1 = _dispatch(x, order, total, K, chunk)
        # Rows past the held ones are never computed: the grouped product
        # leaves them undefined, so every use is masked.
        held = (jnp.arange(S * K) < total)[:, None]

        def gmm(lhs, name):
            with jax.named_scope("expert_gmm"):
                out = kernel_ops.grouped_matmul(lhs, weights[name], sizes,
                                                kernel=self.kernel_config)
            return jnp.where(held, out, 0)

        out = gmm(gmm(rows_0, "wi_0") * jax.nn.silu(gmm(rows_1, "wi_1")),
                  "wo")
        with jax.named_scope("moe.combine"):
            y = _combine(out, gates.reshape(-1), order, total, K, chunk)
        return y, sizes

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        x = self._to_compute(x)
        first, count = cfg.held_experts
        experts, gates = self.router(x)
        held = (experts >= first) & (experts < first + count)
        local = jnp.where(held, experts - first, count)
        weights = {k: self.state[k] for k in self._WEIGHTS}
        seq = jax.checkpoint(self._sequence, prevent_cse=False)
        y, sizes = jax.lax.map(lambda a: seq(weights, *a), (x, local, gates))
        load = jnp.sum(sizes, axis=0).astype(jnp.float32)
        self.add_summary("held_assignments", jnp.sum(load))
        self.add_summary("held_load_max_over_mean",
                         jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0))
        S = x.shape[1]
        chunk = self._chunk_rows(S)
        runs = -(-jnp.sum(sizes, axis=-1) // chunk)
        self.add_summary("held_chunk_share",
                         jnp.mean(runs) * chunk / (S * cfg.top_k))
        y = y.astype(x.dtype)
        if cfg.shared is not None:
            y = y + self.shared_experts(x)
        return remat_name(y, "ffn_out")
