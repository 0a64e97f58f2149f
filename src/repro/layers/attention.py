"""Multi-head / grouped-query attention with an encapsulated KV cache.

Token-mixer interface (shared with Mamba/RWKV so any of them is a drop-in
child of TransformerLayer — the paper's encapsulation claim, §6):

  forward(x, positions=None) -> y                       # full-sequence
  init_states(batch, max_len) -> state                  # empty cache
  prefill(x, positions=None) -> (state, y)              # fill cache
  extend_step(state, x_step) -> (state, y_step)         # decode step(s)

The KV cache layout (dense vs sliding-window ring buffer) is a private
detail of this layer: serving engines only see opaque state pytrees, which
is what lets paged/continuous-batching techniques integrate without touching
model code (paper §6).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, Required, config_class, maybe_set
from repro.core.module import no_context
from repro.core.utils import PartitionSpecLike, remat_name
from repro.core.config import ConfigBase
from repro.kernels import ops as kernel_ops
from repro.layers.base import BaseLayer, KernelConfig, fan_in_init
from repro.layers.basic import Linear
from repro.layers.rope import BaseRotaryEmbedding, RotaryEmbedding
from repro.quantization import kv as kv_quant

__all__ = ["MultiheadAttention"]


class MultiheadAttention(BaseLayer):
    @config_class
    class Config(BaseLayer.Config):
        input_dim: Required[int] = REQUIRED
        num_heads: Required[int] = REQUIRED
        num_kv_heads: Optional[int] = None  # None -> MHA
        head_dim: Optional[int] = None  # None -> input_dim // num_heads
        qkv_bias: bool = False
        out_bias: bool = False
        # Projection template: the DotGeneral-swap point (paper §4.2) — e.g.
        # QuantizedLinear replaces it via one config traversal.
        proj: ConfigBase = Linear.Config()
        # Swappable positional-embedding child; None disables RoPE.
        rope: Optional[BaseRotaryEmbedding.Config] = RotaryEmbedding.Config()
        causal: bool = True
        sliding_window: Optional[int] = None
        logit_softcap: Optional[float] = None
        # None -> 1/sqrt(head_dim); gemma2 overrides (query_pre_attn_scalar).
        query_scale: Optional[float] = None
        # Kernel selection + tiling for attention.fwd / attention.decode:
        # resolved per call by the kernel registry (capability predicates
        # pick Pallas flash / blockwise / ref per platform and feature set).
        # Mesh rules rewrite this tree-wide via KernelModifier (paper §4.2).
        # NOTE: the Pallas decode kernel assumes a replicated KV cache; the
        # layer reports sequence-sharded caches as a feature, so "auto"
        # resolves them to "ref" (whose logits_shard_fn keeps GSPMD in the
        # partial-softmax layout) and explicit "pallas" rejects with reason.
        kernel: KernelConfig = KernelConfig()
        # KV cache layout: "dense" (per-slot (B, Hkv, T, D) ring buffer) |
        # "paged" (shared (P, Hkv, page, D) pool of fixed-size pages +
        # per-sequence page tables, vLLM-style). Paged allocates KV on demand instead of
        # slots x max_len up front — the serving subsystem
        # (repro.serving) packs more concurrent sequences into the same
        # memory and evicts/restores them page-wise. Config choice, not a
        # code change (paper §4.2): engines only see opaque state pytrees.
        kv_cache_layout: str = "dense"
        # Tokens per physical page.
        page_size: int = 16
        # Physical pages in the shared pool (page 0 is reserved as the null
        # target of unmapped table entries and is never written). None ->
        # full residency: 1 + batch_size * ceil(max_len / page_size) pages,
        # which makes generate()-style whole-batch decoding work with the
        # identity page table that init_states installs when capacity
        # allows. The serving allocator sets this BELOW full residency and
        # owns the tables — that undercommit is where the >= 2x concurrency
        # at equal KV memory comes from.
        num_pages: Optional[int] = None
        # Named-axis shardings.
        qkv_weight_partition: PartitionSpecLike = ("data", "model")
        out_weight_partition: PartitionSpecLike = ("model", "data")
        # Activation sharding for (B, S, H*D) projections.
        hidden_partition: PartitionSpecLike = (("pod", "data"), None, "model")
        # KV cache sharding, head-major (B, Hkv, T, D): the decode kernel's
        # K/V tile is then a (tokens, head_dim) slab of the cache itself.
        kv_cache_partition: PartitionSpecLike = (("pod", "data"), "model", None, None)
        kv_cache_dtype: Any = jnp.bfloat16

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        cfg = self.config
        if cfg.num_kv_heads is None:
            cfg.set(num_kv_heads=cfg.num_heads)
        if cfg.head_dim is None:
            cfg.set(head_dim=cfg.input_dim // cfg.num_heads)
        if cfg.num_heads % cfg.num_kv_heads != 0:
            raise ValueError(f"num_heads {cfg.num_heads} % num_kv_heads {cfg.num_kv_heads} != 0")
        if cfg.kv_cache_layout not in ("dense", "paged"):
            raise ValueError(f"Unknown kv_cache_layout {cfg.kv_cache_layout!r}")
        if cfg.kv_cache_layout == "paged" and cfg.sliding_window is not None:
            # The ring buffer IS the memory bound for sliding-window layers;
            # paging them would only add indirection.
            raise ValueError("kv_cache_layout='paged' does not support "
                             "sliding_window; keep the dense ring layout")
        # Quantized-pool format (int8 / fp8-e4m3 with per-slot scales in a
        # scale_pool leaf), or None for plain astype storage. Resolved once,
        # declaratively — the layer never branches on dtype names (that
        # logic is encapsulated in repro.quantization.kv), and an invalid
        # combination (int8 on a dense ring) fails here, at build time.
        self._kv_fmt = kv_quant.pool_format(cfg.kv_cache_dtype,
                                            layout=cfg.kv_cache_layout)
        proj = cfg.proj.clone().set(
            input_dim=cfg.input_dim,
            bias=cfg.qkv_bias,
            weight_partition=cfg.qkv_weight_partition,
            param_dtype=cfg.param_dtype,
        )
        maybe_set(proj, dtype_policy=cfg.dtype_policy)
        self._add_child("q_proj", proj.clone(output_dim=cfg.num_heads * cfg.head_dim))
        self._add_child("k_proj", proj.clone(output_dim=cfg.num_kv_heads * cfg.head_dim))
        self._add_child("v_proj", proj.clone(output_dim=cfg.num_kv_heads * cfg.head_dim))
        self._add_child(
            "o_proj",
            maybe_set(cfg.proj.clone().set(
                input_dim=cfg.num_heads * cfg.head_dim,
                output_dim=cfg.input_dim,
                bias=cfg.out_bias,
                weight_partition=cfg.out_weight_partition,
                param_dtype=cfg.param_dtype,
            ), dtype_policy=cfg.dtype_policy),
        )
        if cfg.rope is not None:
            rope_cfg = cfg.rope.clone()
            if not rope_cfg.dim:
                rope_cfg.set(dim=cfg.head_dim)
            self._add_child("rope", rope_cfg)

    # ------------------------------------------------------------------ utils

    def _project_qkv(self, x: jax.Array, positions: jax.Array):
        cfg = self.config
        x = self._to_compute(x)
        B, S, _ = x.shape
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)
        q = self._shard(q, cfg.hidden_partition)
        k = remat_name(k, "kv_proj")
        q = remat_name(q, "q_proj")
        q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
        k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
        v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
        if "rope" in self._children:
            q = self.rope.apply(q, positions)
            k = self.rope.apply(k, positions)
        return q, k, v

    def _kv_cache_replicated(self) -> bool:
        """Whether the KV cache is unsharded/replicated on the active mesh.

        Reported to the registry as a capability feature: the Pallas decode
        kernel has no shard_map plumbing yet, so a sharded cache would
        silently all-gather per decode step. "auto" resolves sharded caches
        to the ref path (whose logits_shard_fn keeps GSPMD in the
        partial-softmax layout); an explicit Pallas request fails resolution
        with this reason listed.
        """
        from repro.core.utils import current_mesh, resolve_spec

        cfg = self.config
        mesh = current_mesh()
        if mesh is None or cfg.kv_cache_partition is None:
            return True
        spec = resolve_spec(cfg.kv_cache_partition, mesh)

        def size(entry):
            names = entry if isinstance(entry, (tuple, list)) else (entry,)
            n = 1
            for name in names:
                if name is not None:
                    n *= mesh.shape[name]
            return n

        return not any(size(e) > 1 for e in tuple(spec))

    def _attend(self, q, k, v, *, q_positions, k_positions, decode=False,
                page_tables=None, scale_pool=None):
        cfg = self.config
        kwargs = dict(
            q_positions=q_positions,
            k_positions=k_positions,
            causal=cfg.causal,
            sliding_window=cfg.sliding_window,
            logit_softcap=cfg.logit_softcap,
            scale=cfg.query_scale,
        )
        if decode:
            logits_shard_fn = None
            if page_tables is None and cfg.kv_cache_partition is not None:
                kv_spec = tuple(cfg.kv_cache_partition)
                # logits (B, Hkv, G, S', T): batch + cache-seq axes from config.
                spec = (kv_spec[0], None, None, None, kv_spec[2])
                logits_shard_fn = lambda l: self._shard(l, spec)  # noqa: E731
            return kernel_ops.decode_attention(
                q, k, v, page_tables=page_tables, scale_pool=scale_pool,
                replicated_cache=self._kv_cache_replicated(),
                logits_shard_fn=logits_shard_fn,
                kernel=self.kernel_config, **kwargs)
        # (B, S, H, D) sharding: hidden_partition's H*D split falls on heads.
        hp = cfg.hidden_partition
        partition = (*hp, None) if hp is not None else (None,) * 4
        return kernel_ops.flash_attention(
            q, k, v, kernel=self.kernel_config, needs_grad=self.is_training,
            partition=partition, **kwargs)

    # --------------------------------------------------------------- forward

    def forward(self, x: jax.Array, positions: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        B, S, _ = x.shape
        if positions is None:
            positions = jnp.arange(S)
        q, k, v = self._project_qkv(x, positions)
        out = self._attend(q, k, v, q_positions=positions, k_positions=positions)
        out = remat_name(out, "attn_out")
        out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
        out = self._shard(out, cfg.hidden_partition)
        return self.o_proj(out)

    # ---------------------------------------------------------------- decode

    def _cache_len(self, max_len: int) -> int:
        cfg = self.config
        if cfg.sliding_window is not None:
            return min(max_len, cfg.sliding_window)
        return max_len

    def _paged_geometry(self, batch_size: int, max_len: int):
        """(page_size, logical pages per sequence, physical pool pages)."""
        cfg = self.config
        page = cfg.page_size
        n_logical = -(-max_len // page)
        num_pages = cfg.num_pages
        if num_pages is None:
            num_pages = 1 + batch_size * n_logical  # + the reserved null page
        return page, n_logical, num_pages

    @no_context
    def state_partition_specs(self, *_):
        """Named-axis shardings for the init_states pytree (used by launchers
        to build explicit in_shardings for serve_step)."""
        cfg = self.config
        kv = tuple(cfg.kv_cache_partition) if cfg.kv_cache_partition else (None,) * 4
        if cfg.kv_cache_layout == "paged":
            pool = (None, kv[1], None, kv[3])  # (P, Hkv, page, D)
            specs = {"k_pool": pool, "v_pool": pool, "pos_pool": (None, None),
                     "page_table": (kv[0], None), "index": (kv[0],)}
            if self._kv_fmt is not None:
                specs["scale_pool"] = (None, None, None)  # (P, page, 2)
            return specs
        return {"k": kv, "v": kv, "pos": (kv[0], kv[2]), "index": (kv[0],)}

    def init_states(self, batch_size: int, max_len: int) -> Dict[str, Any]:
        """Empty KV cache. ``pos`` tracks the absolute position in each slot
        (-1 = invalid), which makes ring-buffer masking trivial.

        Paged layout: a shared ``(num_pages, Hkv, page_size, D)`` pool, a
        per-page position pool, and per-sequence page tables. Page 0 is the
        reserved null page (unmapped table entries clamp to it on reads and
        are masked; writes through unmapped entries are dropped). When the
        pool is big enough for full residency the tables start as the
        identity layout so plain batched generation works out of the box;
        otherwise they start unmapped (-1) and a serving-side allocator owns
        them.
        """
        cfg = self.config
        if cfg.kv_cache_layout == "paged":
            page, n_logical, P = self._paged_geometry(batch_size, max_len)
            pool_shape = (P, cfg.num_kv_heads, page, cfg.head_dim)
            pool_spec = None
            if cfg.kv_cache_partition is not None:
                kv = tuple(cfg.kv_cache_partition)
                pool_spec = (None, kv[1], None, kv[3])
            if P >= 1 + batch_size * n_logical:
                table = 1 + jnp.arange(batch_size * n_logical, dtype=jnp.int32
                                       ).reshape(batch_size, n_logical)
            else:
                table = jnp.full((batch_size, n_logical), -1, jnp.int32)
            storage = (self._kv_fmt.storage_dtype if self._kv_fmt is not None
                       else cfg.kv_cache_dtype)
            state = {
                "k_pool": self._shard(jnp.zeros(pool_shape, storage),
                                      pool_spec),
                "v_pool": self._shard(jnp.zeros(pool_shape, storage),
                                      pool_spec),
                "pos_pool": jnp.full((P, page), -1, jnp.int32),
                "page_table": table,
                "index": jnp.zeros((batch_size,), jnp.int32),
            }
            if self._kv_fmt is not None:
                state["scale_pool"] = kv_quant.init_scale_pool(P, page)
            return state
        T = self._cache_len(max_len)
        shape = (batch_size, cfg.num_kv_heads, T, cfg.head_dim)
        cache = {
            "k": jnp.zeros(shape, cfg.kv_cache_dtype),
            "v": jnp.zeros(shape, cfg.kv_cache_dtype),
            # Per-row slot positions/index: continuous batching admits new
            # requests into individual slots mid-flight (paper §6).
            "pos": jnp.full((batch_size, T), -1, jnp.int32),
            "index": jnp.zeros((batch_size,), jnp.int32),
        }
        cache["k"] = self._shard(cache["k"], cfg.kv_cache_partition)
        cache["v"] = self._shard(cache["v"], cfg.kv_cache_partition)
        return cache

    def _paged_scatter(self, state: Dict[str, Any], k: jax.Array,
                       v: jax.Array, positions: jax.Array,
                       valid: jax.Array) -> Dict[str, Any]:
        """Write tokens at absolute ``positions`` (B, S) into the page pool
        through each sequence's page table row. Tokens that are invalid
        (bucket padding) or whose logical page is unmapped scatter out of
        bounds and are dropped — unmapped writes can never corrupt the null
        page or another sequence's pages.
        """
        cfg = self.config
        table = state["page_table"]  # (B, N)
        P, page = state["pos_pool"].shape
        # Positions beyond table capacity (no ring in the paged layout) are
        # dropped, like bucket padding.
        valid = valid & (positions >= 0) & (positions < table.shape[1] * page)
        logical = jnp.clip(positions // page, 0, table.shape[1] - 1)
        phys = jnp.take_along_axis(table, logical, axis=1)  # (B, S)
        phys = jnp.where(valid & (phys > 0), phys, P)  # page 0 = null
        slot = positions % page
        flat = phys * page + slot
        oob = P * page
        if self._kv_fmt is not None:
            # Quantize-on-write: per-token-slot scales scatter through the
            # same (OOB-dropping) flat index as the payload, so a dropped
            # write drops its scale too. Deterministic quantization is what
            # keeps prefix hits exact: a shared page holds bitwise the same
            # bytes a cold prefill would produce.
            k_st, v_st, scales = kv_quant.quantize_kv_write(k, v,
                                                            self._kv_fmt)
        else:
            k_st = k.astype(cfg.kv_cache_dtype)
            v_st = v.astype(cfg.kv_cache_dtype)
            scales = None
        # Head-major pools: index (page, :, slot) gathers the (B, S) token
        # rows of every head, matching the (B, S, Hkv, D) update.
        new_k = state["k_pool"].at[phys, :, slot].set(k_st, mode="drop")
        new_v = state["v_pool"].at[phys, :, slot].set(v_st, mode="drop")
        new_pos = state["pos_pool"].reshape(oob).at[flat].set(
            positions.astype(jnp.int32), mode="drop").reshape(P, page)
        pools = {"k_pool": new_k, "v_pool": new_v, "pos_pool": new_pos}
        if scales is not None:
            pools["scale_pool"] = state["scale_pool"].reshape(oob, 2).at[
                flat].set(scales, mode="drop").reshape(P, page, 2)
        return pools

    def prefill(self, state: Dict[str, Any], x: jax.Array,
                positions: Optional[jax.Array] = None,
                length: Optional[jax.Array] = None
                ) -> Tuple[Dict[str, Any], jax.Array]:
        """Runs the full forward over the prompt and fills the cache.

        ``length`` (optional scalar) marks only the first ``length`` tokens
        of ``x`` as real: trailing bucket padding is neither written to the
        cache (its scatter indices land out of bounds and are dropped) nor
        counted in ``index``. This is what lets the serving engine admit
        prompts through a small set of power-of-two padded shapes (one
        compile per bucket) without polluting the cache.
        """
        cfg = self.config
        B, S, _ = x.shape
        if positions is None:
            positions = jnp.arange(S)
        q, k, v = self._project_qkv(x, positions)
        out = self._attend(q, k, v, q_positions=positions, k_positions=positions)
        out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
        y = self.o_proj(out)

        length = jnp.asarray(S if length is None else length, jnp.int32)
        if cfg.kv_cache_layout == "paged":
            pos_b = jnp.broadcast_to(positions, (B, S))
            pools = self._paged_scatter(state, k, v, pos_b,
                                        valid=pos_b < length)
            return {**pools, "page_table": state["page_table"],
                    "index": jnp.broadcast_to(length, (B,))}, y
        T = state["k"].shape[2]
        if S > T:
            # Ring layout: keep the last T *valid* tokens.
            start = jnp.clip(length - T, 0, S - T)
            k_keep = jax.lax.dynamic_slice_in_dim(k, start, T, axis=1)
            v_keep = jax.lax.dynamic_slice_in_dim(v, start, T, axis=1)
            p_keep = jax.lax.dynamic_slice_in_dim(positions, start, T, axis=0)
        else:
            k_keep, v_keep, p_keep = k, v, positions
        valid = p_keep < length
        # Invalid tokens scatter to index T (out of bounds -> dropped), so
        # bucket padding never overwrites live ring slots.
        slots = jnp.where(valid, p_keep % T, T)
        new_k = state["k"].at[:, :, slots].set(
            jnp.swapaxes(k_keep, 1, 2).astype(cfg.kv_cache_dtype))
        new_v = state["v"].at[:, :, slots].set(
            jnp.swapaxes(v_keep, 1, 2).astype(cfg.kv_cache_dtype))
        new_pos = state["pos"].at[:, slots].set(p_keep.astype(jnp.int32)[None, :])
        new_state = {
            "k": self._shard(new_k, cfg.kv_cache_partition),
            "v": self._shard(new_v, cfg.kv_cache_partition),
            "pos": new_pos,
            "index": jnp.broadcast_to(length, (B,)),
        }
        return new_state, y

    def extend_step(self, state: Dict[str, Any], x_step: jax.Array
                    ) -> Tuple[Dict[str, Any], jax.Array]:
        """Decode S' >= 1 new tokens against the cache.

        S' > 1 with causal masking among the new tokens doubles as the
        *chunked-prefill* program: the serving scheduler feeds prompt chunks
        through this path so a long prompt never stalls in-flight decodes.
        """
        cfg = self.config
        B, S_new, _ = x_step.shape
        index = state["index"]  # (B,)
        positions = index[:, None] + jnp.arange(S_new)[None, :]  # (B, S')
        q, k, v = self._project_qkv(x_step, positions)

        if cfg.kv_cache_layout == "paged":
            pools = self._paged_scatter(
                state, k, v, positions, valid=jnp.ones_like(positions, bool))
            out = self._attend(
                q, pools["k_pool"], pools["v_pool"],
                q_positions=positions, k_positions=pools["pos_pool"],
                page_tables=state["page_table"],
                scale_pool=pools.get("scale_pool"), decode=True)
            out = out.reshape(B, S_new, cfg.num_heads * cfg.head_dim)
            return {**pools, "page_table": state["page_table"],
                    "index": index + S_new}, self.o_proj(out)

        T = state["k"].shape[2]
        slots = positions % T  # (B, S')
        rows = jnp.arange(B)[:, None]
        # (rows, :, slots) indexes (B, S') token rows of every head.
        new_k = state["k"].at[rows, :, slots].set(k.astype(cfg.kv_cache_dtype))
        new_v = state["v"].at[rows, :, slots].set(v.astype(cfg.kv_cache_dtype))
        new_pos = state["pos"].at[rows, slots].set(positions.astype(jnp.int32))

        out = self._attend(
            q,
            new_k.astype(q.dtype),
            new_v.astype(q.dtype),
            q_positions=positions,
            k_positions=new_pos,
            decode=True,
        )
        out = out.reshape(B, S_new, cfg.num_heads * cfg.head_dim)
        y = self.o_proj(out)
        new_state = {
            "k": self._shard(new_k, cfg.kv_cache_partition),
            "v": self._shard(new_v, cfg.kv_cache_partition),
            "pos": new_pos,
            "index": index + S_new,
        }
        return new_state, y
