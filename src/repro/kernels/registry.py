"""Hardware-agnostic kernel registry: capability-based dispatch (paper §4.2).

The paper's claim is that per-backend kernel choices (cuDNN / NKI /
SplashAttention / Pallas) live in ~10 lines of mesh-rule config, never in
model code. This module is the mechanism: every kernel implementation
registers a :class:`KernelSpec` — op name, backend id, supported platforms,
and a *capability predicate* over the call's features — and
:func:`resolve` picks the highest-priority eligible implementation for the
detected platform. Layers never branch on impl strings; they carry one
:class:`KernelConfig` sub-config and call the dispatchers in
``repro.kernels.ops``.

Adding a backend = registering specs in one file + (optionally) one mesh
rule that rewrites ``KernelConfig`` — zero model-code changes.

Ops and backends registered here:

  op                 backends (priority order)
  ----------------   -----------------------------------------
  attention.fwd      pallas > pallas:interpret > blockwise > ref
  attention.decode   pallas > pallas:interpret > ref
  rmsnorm            pallas > pallas:interpret > ref
  wkv6               pallas > pallas:interpret > ref
  wkv6.decode        ref (O(1) recurrent step)
  moe.gmm            pallas > pallas:interpret > ref (ragged_dot)

``ref`` backends are pure-XLA and eligible everywhere; they are also the
numerical oracles (``repro.kernels.ref``). ``pallas:interpret`` runs the
Mosaic kernels through the Pallas interpreter on any platform — it is never
auto-selected unless ``KernelConfig.interpret=True`` (it is slow), but can
always be requested explicitly.

Resolution is memoized: the (op, backend, features) triple is hashable and
the cached lookup is a single dict hit, so dispatch adds no per-call or
per-trace overhead on hot paths.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ConfigBase, config_class

__all__ = [
    "KernelConfig",
    "KernelFeatures",
    "KernelSpec",
    "KernelDispatchError",
    "register",
    "resolve",
    "resolve_backend",
    "registered_ops",
    "registered_backends",
    "clear_dispatch_cache",
    "dispatch_cache_stats",
]

# ---------------------------------------------------------------------------
# The one kernel config every kernel-calling layer shares (tentpole API).
# ---------------------------------------------------------------------------


@config_class
class KernelConfig(ConfigBase):
    """Unified kernel selection + tiling config (replaces the old scattered
    ``impl`` / ``decode_impl`` / ``kernel_interpret`` / ``blockwise_chunk_size``
    knobs).

    ``backend``: "auto" resolves per-op against the registry for the current
        platform; any registered backend id ("pallas", "pallas:interpret",
        "blockwise", "ref") forces that backend for every op this layer calls
        (resolution errors list each rejected candidate with its reason).
    ``op_overrides``: per-op backend ids, taking precedence over ``backend``
        (e.g. ``{"attention.decode": "pallas"}``).
    ``interpret``: run Pallas kernels through the interpreter (validation
        off-TPU). Also lets "auto" select the interpret backend, and turns an
        explicit "pallas" selection into "pallas:interpret".
    The remaining fields are per-backend tiling knobs — a per-hardware tiling
    table is one ``KernelModifier`` mesh rule away.
    """

    backend: str = "auto"
    op_overrides: Optional[Dict[str, str]] = None
    interpret: bool = False
    # Pallas flash-attention forward/backward tiles.
    block_q: int = 128
    block_k: int = 128
    # Pallas flash-decode KV tile.
    decode_block_k: int = 256
    # XLA blockwise attention (query-chunked scan).
    blockwise_chunk_size: int = 512
    # WKV6 chunk length (Pallas grid / ref scan).
    wkv_chunk_size: int = 64
    # Pallas RMSNorm row tile.
    rmsnorm_block_rows: int = 256

    def backend_for(self, op: str) -> str:
        """The backend id this config requests for ``op`` ("auto" included).

        ``interpret=True`` turns a "pallas" request into "pallas:interpret"
        so explicit pallas selections stay runnable off-TPU.
        """
        backend = self.backend
        if self.op_overrides:
            backend = self.op_overrides.get(op, backend)
        if backend == "pallas" and self.interpret:
            backend = "pallas:interpret"
        return backend


# ---------------------------------------------------------------------------
# Features + specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelFeatures:
    """Hashable description of one kernel call site, as seen at trace time.

    Capability predicates accept/reject on these. ``explicit`` is set by
    :func:`resolve` when the caller named a backend — predicates may waive
    *heuristic* rejections (e.g. "1-token query is GEMV-bound") for explicit
    requests, but must keep *correctness* rejections unconditional.
    """

    platform: str = "cpu"  # jax.default_backend(): "cpu" | "tpu" | "gpu"
    dtype: str = "float32"
    interpret: bool = False
    explicit: bool = False
    needs_grad: bool = False
    # q/k positions are not provably the same contiguous stream.
    ragged_positions: bool = False
    # 1-token query (decode-shaped call into the full-sequence op).
    single_query: bool = False
    paged: bool = False
    sliding_window: bool = False
    # KV cache is replicated / unsharded across the mesh (decode ops).
    replicated_cache: bool = True
    # S' > 1 query into the decode op: a chunked-prefill or speculative
    # draft-verify window rather than a 1-token step. Lets backends pick
    # different tiling (the query dim becomes a real matmul dim) and lets
    # the dispatch cache keep verify- and decode-shaped resolutions apart.
    multi_query: bool = False
    # KV cache *storage* dtype (decode ops). Quantized pools ("int8",
    # "float8_e4m3fn") carry per-slot scales in a scale_pool leaf and need
    # a backend that dequantizes — in-kernel (pallas paged) or at gather
    # (ref); plain float caches are a pass-through astype.
    kv_dtype: str = "float32"

    def __post_init__(self):
        # Hash once at construction: dispatch-cache lookups are on the
        # trace hot path and must not re-hash 12 fields per call.
        object.__setattr__(self, "_hash", hash((
            self.platform, self.dtype, self.interpret, self.explicit,
            self.needs_grad, self.ragged_positions, self.single_query,
            self.paged, self.sliding_window, self.replicated_cache,
            self.multi_query, self.kv_dtype)))

    def __hash__(self):  # noqa: D105 — dataclass respects explicit __hash__
        return self._hash


# A predicate returns None (eligible) or a human-readable rejection reason.
CapabilityPredicate = Callable[[KernelFeatures], Optional[str]]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel implementation."""

    op: str
    backend: str
    fn: Optional[Callable]
    # Platform names this impl lowers on; "*" = any.
    platforms: Tuple[str, ...] = ("*",)
    priority: int = 0
    supports: Optional[CapabilityPredicate] = None
    # GSPMD cannot partition this impl (a Mosaic kernel call): on a
    # multi-device mesh the dispatcher runs it per shard (``per_shard``),
    # split as the calling layer's partition says.
    per_shard: bool = False

    def rejection_reason(self, features: KernelFeatures) -> Optional[str]:
        """None if eligible for ``features``, else why not."""
        if "*" not in self.platforms and features.platform not in self.platforms:
            return (f"requires platform in {list(self.platforms)} "
                    f"(running on {features.platform!r})")
        if self.supports is not None:
            return self.supports(features)
        return None


class KernelDispatchError(RuntimeError):
    """No eligible kernel: the message enumerates every candidate and the
    reason it was rejected (the registry's debuggability contract)."""


_REGISTRY: Dict[str, Dict[str, KernelSpec]] = {}
_DISPATCH_CACHE: Dict[Tuple[str, str, KernelFeatures], KernelSpec] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def register(spec: KernelSpec) -> KernelSpec:
    """Registers (or replaces) ``spec`` under (op, backend) and clears the
    dispatch cache. Replacement is what lets a new backend file override or
    extend the built-ins without editing this module."""
    _REGISTRY.setdefault(spec.op, {})[spec.backend] = spec
    _DISPATCH_CACHE.clear()
    return spec


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


def registered_backends(op: str) -> List[str]:
    """Backend ids registered for ``op``, highest priority first."""
    specs = _op_specs(op)
    return [s.backend for s in sorted(specs.values(),
                                      key=lambda s: -s.priority)]


def clear_dispatch_cache() -> None:
    _DISPATCH_CACHE.clear()
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0


def dispatch_cache_stats() -> Dict[str, Any]:
    """Hit/miss counts, the cache size, and under ``resolved`` every
    memoized resolution as ``(op, requested backend, features, chosen
    backend)`` — what each call site actually ran."""
    return dict(_CACHE_STATS, size=len(_DISPATCH_CACHE),
                resolved=[(op, requested, feats, spec.backend)
                          for (op, requested, feats), spec
                          in _DISPATCH_CACHE.items()])


def _op_specs(op: str) -> Dict[str, KernelSpec]:
    if op not in _REGISTRY:
        raise KernelDispatchError(
            f"Unknown kernel op {op!r}; registered ops: {registered_ops()}")
    return _REGISTRY[op]


def resolve(op: str, features: KernelFeatures, *,
            backend: str = "auto") -> KernelSpec:
    """Picks the implementation of ``op`` for ``features``.

    ``backend="auto"``: the highest-priority eligible spec.
    ``backend=<id>``: that spec, eligibility still enforced (explicit
    requests set ``features.explicit`` so heuristic-only rejections are
    waived; correctness rejections still raise).

    Raises :class:`KernelDispatchError` listing every candidate and why it
    was rejected when nothing is eligible.
    """
    key = (op, backend, features)
    try:
        cached = _DISPATCH_CACHE[key]
        _CACHE_STATS["hits"] += 1
        return cached
    except KeyError:
        _CACHE_STATS["misses"] += 1

    specs = _op_specs(op)
    rejected: List[Tuple[KernelSpec, str]] = []
    chosen: Optional[KernelSpec] = None

    if backend != "auto":
        feats = dataclasses.replace(features, explicit=True)
        target = specs.get(backend)
        if target is None:
            raise KernelDispatchError(
                f"Unknown backend {backend!r} for op {op!r}; registered "
                f"backends: {registered_backends(op)}")
        reason = target.rejection_reason(feats)
        if reason is None:
            chosen = target
        else:
            rejected.append((target, reason))
            for spec in specs.values():
                if spec is not target:
                    rejected.append(
                        (spec, f"excluded by explicit backend={backend!r}"))
    else:
        for spec in sorted(specs.values(), key=lambda s: -s.priority):
            reason = spec.rejection_reason(features)
            if reason is None:
                chosen = spec
                break
            rejected.append((spec, reason))

    if chosen is None:
        lines = [f"No eligible kernel for op {op!r} "
                 f"(backend={backend!r}, platform={features.platform!r}). "
                 f"Candidates:"]
        for spec, reason in rejected:
            lines.append(f"  - {spec.backend} (priority {spec.priority}): "
                         f"{reason}")
        lines.append(f"  features: {features}")
        raise KernelDispatchError("\n".join(lines))

    _DISPATCH_CACHE[key] = chosen
    return chosen


def resolve_backend(op: str, features: KernelFeatures,
                    cfg: Optional[KernelConfig] = None) -> KernelSpec:
    """Convenience: resolve ``op`` under a :class:`KernelConfig` (or the
    defaults when ``cfg`` is None), folding the config's interpret flag and
    per-op override into the feature set.

    A *layer-wide* ``cfg.backend`` is a preference across heterogeneous ops:
    ops that don't register that backend at all (e.g. ``backend="blockwise"``
    on a layer that also dispatches ``attention.decode``, or ``"pallas"`` on
    the ref-only ``wkv6.decode`` recurrence) fall back to auto resolution
    instead of erroring. Per-op ``op_overrides`` stay strict — they name the
    op, so an unknown backend there is a config bug and raises.
    """
    cfg = cfg if cfg is not None else DEFAULT_CONFIG
    features = dataclasses.replace(features, interpret=cfg.interpret)
    backend = cfg.backend_for(op)
    if (backend != "auto"
            and not (cfg.op_overrides and op in cfg.op_overrides)
            and backend not in _op_specs(op)):
        backend = "auto"
    return resolve(op, features, backend=backend)


# Shared registry-default config for callers that pass kernel=None.
# Read-only by convention: never mutate (layers own their KernelConfig).
DEFAULT_CONFIG = KernelConfig()


def current_platform() -> str:
    return jax.default_backend()


# ---------------------------------------------------------------------------
# Shared predicate pieces
# ---------------------------------------------------------------------------


def _pallas_gate(features: KernelFeatures) -> Optional[str]:
    """Common gate for real (non-interpret) Mosaic kernels."""
    if features.interpret:
        return ("interpret mode requested (kernel.interpret=True): use "
                "backend 'pallas:interpret'")
    if features.platform != "tpu":
        return (f"Pallas Mosaic kernels lower on TPU only (running on "
                f"{features.platform!r}); use 'pallas:interpret' off-TPU")
    return None


def _interpret_gate(features: KernelFeatures) -> Optional[str]:
    """Interpret-mode kernels run anywhere but are validation-speed: never
    auto-selected unless the config asks for interpret mode."""
    if not (features.interpret or features.explicit):
        return ("interpret-mode backend is not auto-selected; set "
                "kernel.interpret=True or select 'pallas:interpret' "
                "explicitly")
    return None


def _flash_fwd_caps(features: KernelFeatures) -> Optional[str]:
    """Capabilities of the flash-attention forward kernel (either mode)."""
    if features.ragged_positions:
        # Correctness: the kernel assumes q/k share one contiguous position
        # stream. Unconditional, even for explicit requests.
        return ("q/k positions are not provably identical (ragged/decode "
                "call): the contiguous flash kernel does not apply")
    if features.paged:
        return "paged KV is a decode-op feature (use op 'attention.decode')"
    if features.single_query and not features.explicit:
        # Heuristic: a 1-token query is GEMV-bound, not a flash shape.
        return "1-token query is GEMV-bound; ref/blockwise is faster"
    return None


def _flash_decode_caps(features: KernelFeatures) -> Optional[str]:
    if not features.replicated_cache:
        # Correctness/perf cliff: no shard_map plumbing yet — a sharded KV
        # cache would silently all-gather per decode step.
        return ("flash-decode requires an unsharded/replicated KV cache "
                "(no shard_map plumbing); 'ref' keeps GSPMD in the "
                "partial-softmax layout for sequence-sharded caches")
    if features.needs_grad:
        return "flash-decode is forward-only (no custom VJP)"
    if features.kv_dtype == "int8" and not features.paged:
        # Correctness: int8 KV is only meaningful with the per-slot scale
        # rows that live in the paged pool; a dense int8 cache has no
        # scales to dequantize with. Unconditional.
        return ("int8 KV storage requires the paged layout (scale_pool "
                "carries the per-slot scales)")
    return None


def _forward_only(what: str) -> CapabilityPredicate:
    def pred(features: KernelFeatures) -> Optional[str]:
        if features.needs_grad:
            return f"{what} is forward-only (no custom VJP); ref autodiffs"
        return None

    return pred


def _chain(*preds: CapabilityPredicate) -> CapabilityPredicate:
    def pred(features: KernelFeatures) -> Optional[str]:
        for p in preds:
            reason = p(features)
            if reason is not None:
                return reason
        return None

    return pred


# ---------------------------------------------------------------------------
# Built-in registrations (the four ops). Adapters normalize every backend to
# one uniform per-op call signature so ops.py stays a thin dispatcher.
# ---------------------------------------------------------------------------


def per_shard(fn: Callable, partition, *arrays,
              whole_dims: Tuple[int, ...] = ()):
    """Runs ``fn(*arrays)`` on each device's shard of the ambient mesh.

    ``partition`` (axis names per dim, as layers state their shardings)
    splits every array and the result alike; ``fn`` sees one shard. Without
    a mesh, or on one device, ``fn`` runs as is. A split that ``fn`` cannot
    take raises: one of ``whole_dims``, or a dim whose size the devices do
    not divide (silently replicating it would make every device compute the
    whole call).
    """
    from repro.core.utils import current_mesh, resolve_spec

    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*arrays)
    if partition is None:
        raise ValueError(
            f"a kernel GSPMD cannot partition runs on a {mesh.size}-device "
            "mesh: the caller must state its partition")
    spec = resolve_spec(partition, mesh)
    for x in arrays:
        for i, (dim, entry) in enumerate(zip(x.shape, spec)):
            names = entry if isinstance(entry, tuple) else (entry,)
            n = math.prod(mesh.shape[a] for a in names if a is not None)
            if n > 1 and (i in whole_dims or dim % n):
                raise ValueError(
                    f"partition {tuple(spec)} splits dim {i} of {x.shape} "
                    f"over {n} devices; the kernel needs it "
                    + ("whole" if i in whole_dims else "to divide"))
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(arrays),
                         out_specs=spec, check_vma=False)(*arrays)


def _register_builtin_specs() -> None:
    from repro.kernels import ref as _ref
    from repro.kernels.flash_attention import flash_attention as _flash_vjp
    from repro.kernels.flash_decode import (
        flash_decode_forward,
        paged_flash_decode_forward,
    )
    from repro.kernels.gmm import grouped_matmul
    from repro.kernels.rmsnorm import rmsnorm_forward
    from repro.kernels.wkv6 import wkv6_forward

    # ---- attention.fwd --------------------------------------------------
    # fn(q, k, v, *, q_positions, k_positions, causal, sliding_window,
    #    logit_softcap, scale, cfg)

    def _fwd_pallas(interpret):
        def fn(q, k, v, *, q_positions, k_positions, causal, sliding_window,
               logit_softcap, scale, cfg):
            del q_positions, k_positions  # provably contiguous (predicate)
            return _flash_vjp(
                q, k, v, causal=causal, sliding_window=sliding_window,
                logit_softcap=logit_softcap, scale=scale,
                block_q=cfg.block_q, block_k=cfg.block_k, interpret=interpret)

        return fn

    def _fwd_blockwise(q, k, v, *, q_positions, k_positions, causal,
                       sliding_window, logit_softcap, scale, cfg):
        return _ref.blockwise_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, sliding_window=sliding_window,
            logit_softcap=logit_softcap, scale=scale,
            chunk_size=cfg.blockwise_chunk_size)

    def _fwd_ref(q, k, v, *, q_positions, k_positions, causal,
                 sliding_window, logit_softcap, scale, cfg):
        del cfg
        return _ref.reference_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, sliding_window=sliding_window,
            logit_softcap=logit_softcap, scale=scale)

    register(KernelSpec(
        op="attention.fwd", backend="pallas", fn=_fwd_pallas(False),
        platforms=("tpu",), priority=100,
        supports=_chain(_pallas_gate, _flash_fwd_caps), per_shard=True))
    # Interpreted, the kernel runs per shard too: CPU tests exercise the
    # chip's multi-device path.
    register(KernelSpec(
        op="attention.fwd", backend="pallas:interpret", fn=_fwd_pallas(True),
        platforms=("*",), priority=90,
        supports=_chain(_interpret_gate, _flash_fwd_caps), per_shard=True))
    register(KernelSpec(
        op="attention.fwd", backend="blockwise", fn=_fwd_blockwise,
        platforms=("*",), priority=50))
    register(KernelSpec(
        op="attention.fwd", backend="ref", fn=_fwd_ref,
        platforms=("*",), priority=0))

    # ---- attention.decode ----------------------------------------------
    # fn(q, k, v, *, q_positions, k_positions, page_tables, scale_pool,
    #    causal, sliding_window, logit_softcap, scale, logits_shard_fn, cfg)

    def _decode_pallas(interpret):
        def fn(q, k, v, *, q_positions, k_positions, page_tables, scale_pool,
               causal, sliding_window, logit_softcap, scale, logits_shard_fn,
               cfg):
            del logits_shard_fn  # replicated cache (predicate-enforced)
            if page_tables is not None:
                return paged_flash_decode_forward(
                    q, k, v, k_positions, page_tables, q_positions,
                    scale_pool=scale_pool,
                    causal=causal, sliding_window=sliding_window,
                    logit_softcap=logit_softcap, scale=scale,
                    interpret=interpret)
            # Contiguous (dense-cache) decode never carries scales (the
            # kv_dtype capability gate rejects quantized dense caches).
            return flash_decode_forward(
                q, k, v, q_positions, k_positions, causal=causal,
                sliding_window=sliding_window, logit_softcap=logit_softcap,
                scale=scale, block_k=cfg.decode_block_k, interpret=interpret)

        return fn

    def _decode_ref(q, k, v, *, q_positions, k_positions, page_tables,
                    scale_pool, causal, sliding_window, logit_softcap, scale,
                    logits_shard_fn, cfg):
        del cfg
        if page_tables is not None:
            # Portable paged path: materialize this batch's pages with an
            # XLA gather (dequantizing through scale_pool when the pool is
            # quantized), then run the reference oracle.
            from repro.kernels import ops as kernel_ops

            k, v, k_positions = kernel_ops.paged_gather_kv(
                k, v, k_positions, page_tables, scale_pool=scale_pool)
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
            logits_shard_fn = None
        else:
            # Head-major (B, Hkv, T, D) cache -> the oracle's (B, T, Hkv, D);
            # XLA folds the transpose into the logits dot.
            k = jnp.swapaxes(k, 1, 2)
            v = jnp.swapaxes(v, 1, 2)
        return _ref.reference_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, sliding_window=sliding_window,
            logit_softcap=logit_softcap, scale=scale,
            logits_shard_fn=logits_shard_fn)

    register(KernelSpec(
        op="attention.decode", backend="pallas", fn=_decode_pallas(False),
        platforms=("tpu",), priority=100,
        supports=_chain(_pallas_gate, _flash_decode_caps)))
    register(KernelSpec(
        op="attention.decode", backend="pallas:interpret",
        fn=_decode_pallas(True), platforms=("*",), priority=90,
        supports=_chain(_interpret_gate, _flash_decode_caps)))
    register(KernelSpec(
        op="attention.decode", backend="ref", fn=_decode_ref,
        platforms=("*",), priority=0))

    # ---- rmsnorm --------------------------------------------------------
    # fn(x, scale, *, eps, cfg)

    def _rmsnorm_pallas(interpret):
        def fn(x, scale, *, eps, cfg):
            return rmsnorm_forward(x, scale, eps=eps,
                                   block_rows=cfg.rmsnorm_block_rows,
                                   interpret=interpret)

        return fn

    def _rmsnorm_ref(x, scale, *, eps, cfg):
        del cfg
        return _ref.reference_rmsnorm(x, scale, eps=eps)

    register(KernelSpec(
        op="rmsnorm", backend="pallas", fn=_rmsnorm_pallas(False),
        platforms=("tpu",), priority=100,
        supports=_chain(_pallas_gate, _forward_only("rmsnorm kernel"))))
    register(KernelSpec(
        op="rmsnorm", backend="pallas:interpret", fn=_rmsnorm_pallas(True),
        platforms=("*",), priority=90,
        supports=_chain(_interpret_gate, _forward_only("rmsnorm kernel"))))
    register(KernelSpec(
        op="rmsnorm", backend="ref", fn=_rmsnorm_ref,
        platforms=("*",), priority=0))

    # ---- wkv6 -----------------------------------------------------------
    # fn(r, k, v, w, u, state, *, cfg)

    def _wkv6_pallas(interpret):
        def fn(r, k, v, w, u, state, *, cfg):
            return wkv6_forward(r, k, v, w, u, state,
                                chunk_size=cfg.wkv_chunk_size,
                                interpret=interpret)

        return fn

    def _wkv6_ref(r, k, v, w, u, state, *, cfg):
        return _ref.reference_wkv6(r, k, v, w, u, state,
                                   chunk_size=cfg.wkv_chunk_size)

    # wkv6.decode: the O(1) recurrent step (ref-only today — a Pallas
    # recurrent-step kernel registers here without touching rwkv.py).
    def _wkv6_decode_ref(r, k, v, w, u, state, *, cfg):
        del cfg
        return _ref.reference_wkv6_recurrent(r, k, v, w, u, state)

    register(KernelSpec(
        op="wkv6.decode", backend="ref", fn=_wkv6_decode_ref,
        platforms=("*",), priority=0))

    wkv_caps = _forward_only("wkv6 kernel")
    register(KernelSpec(
        op="wkv6", backend="pallas", fn=_wkv6_pallas(False),
        platforms=("tpu",), priority=100,
        supports=_chain(_pallas_gate, wkv_caps)))
    register(KernelSpec(
        op="wkv6", backend="pallas:interpret", fn=_wkv6_pallas(True),
        platforms=("*",), priority=90,
        supports=_chain(_interpret_gate, wkv_caps)))
    register(KernelSpec(
        op="wkv6", backend="ref", fn=_wkv6_ref,
        platforms=("*",), priority=0))

    # ---- moe.gmm --------------------------------------------------------
    # fn(lhs, rhs, group_sizes, *, cfg): rows past the groups are undefined.

    def _gmm_pallas(interpret):
        def fn(lhs, rhs, group_sizes, *, cfg):
            del cfg
            return grouped_matmul(lhs, rhs, group_sizes, interpret=interpret)

        return fn

    def _gmm_ref(lhs, rhs, group_sizes, *, cfg):
        del cfg
        return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes,
                                  preferred_element_type=lhs.dtype)

    register(KernelSpec(
        op="moe.gmm", backend="pallas", fn=_gmm_pallas(False),
        platforms=("tpu",), priority=100, supports=_pallas_gate))
    register(KernelSpec(
        op="moe.gmm", backend="pallas:interpret", fn=_gmm_pallas(True),
        platforms=("*",), priority=90, supports=_interpret_gate))
    register(KernelSpec(
        op="moe.gmm", backend="ref", fn=_gmm_ref,
        platforms=("*",), priority=0))


_register_builtin_specs()
