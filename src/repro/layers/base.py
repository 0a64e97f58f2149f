"""BaseLayer: parameterized modules with spec-driven init and sharding.

Parameters are declared as :class:`ParameterSpec` (shape, dtype, initializer,
``mesh_axes``) — the mesh_axes carry the *named-axis* partition spec that the
paper's config-based parallelism (§4.2) hinges on. The trainer consumes
the spec tree to build NamedShardings; layers never touch devices.

Mixed precision is a :class:`DtypePolicy` carried by every layer config:
inputs are cast to ``compute_dtype`` at module boundaries (layers already
cast their params to the input dtype at use-sites, so params follow), while
fp32 islands — norms, softmax, routing, the loss — keep their explicit
accumulation dtypes. Setting bf16-compute/fp32-master training for an entire
model is therefore one ``visit_config`` pass over the trainer config
(``trainer.mesh_rules.DtypePolicyModifier``), never a layer edit — the
paper's ~10-LoC cross-cutting-change mechanism (§4.2) applied to precision.

Kernel selection follows the same pattern: kernel-calling layers declare a
``kernel: KernelConfig`` field and dispatch through ``repro.kernels.ops``
into the capability-based registry; ``KernelModifier`` rewrites every
``KernelConfig`` in the tree from one mesh rule (§4.2 applied to kernels).
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.config import REQUIRED, ConfigBase, Required, config_class
from repro.core.module import Module
from repro.core.utils import PartitionSpecLike, maybe_shard
from repro.kernels.registry import DEFAULT_CONFIG as _DEFAULT_KERNEL_CONFIG
from repro.kernels.registry import KernelConfig

__all__ = [
    "ParameterSpec",
    "DtypePolicy",
    "bf16_policy",
    "KernelConfig",
    "BaseLayer",
    "Initializer",
    "constant_init",
    "zeros_init",
    "ones_init",
    "normal_init",
    "fan_in_init",
    "uniform_scale_init",
]


@config_class
class DtypePolicy(ConfigBase):
    """Per-layer mixed-precision policy (all fields None = current behaviour).

    ``param_dtype``: storage dtype of params that follow the layer's default
        param dtype (explicit fp32 islands like Mamba's ``A_log`` keep their
        declared dtype). None keeps each layer's ``param_dtype`` field.
    ``compute_dtype``: floating inputs are cast to this dtype at every module
        boundary; params follow via the existing ``astype(x.dtype)``
        use-site casts. None = compute follows inputs untouched.
    ``output_dtype``: dtype of model *outputs* (logits); applied by heads.
        None = leave in compute dtype.
    ``grad_dtype``: dtype gradients are accumulated in (grad-accumulation
        buffers; the trainer reads it via ``DtypePolicyModifier``). None =
        accumulate in the param dtype.
    ``fp8``: fp8 compute mode — a :class:`repro.quantization.fp8.Fp8Config`.
        GEMM-boundary layers (``_fp8_boundary = True``, e.g. Linear)
        fake-quantize their inputs to the e4m3 grid with per-tensor
        *delayed* scaling; the amax history rides in layer state. None =
        off. Set tree-wide by ``quantization.modifier.QuantizationModifier``.
    """

    param_dtype: Optional[Any] = None
    compute_dtype: Optional[Any] = None
    output_dtype: Optional[Any] = None
    grad_dtype: Optional[Any] = None
    fp8: Optional[Any] = None


def bf16_policy() -> DtypePolicy:
    """The production default: fp32 master params, bf16 compute, fp32 grad
    accumulation (grad_dtype=None -> param dtype)."""
    return DtypePolicy().set(compute_dtype=jnp.bfloat16)

Initializer = Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]


def zeros_init() -> Initializer:
    return lambda key, shape, dtype: jnp.zeros(shape, dtype)


def ones_init() -> Initializer:
    return lambda key, shape, dtype: jnp.ones(shape, dtype)


def constant_init(value: float) -> Initializer:
    return lambda key, shape, dtype: jnp.full(shape, value, dtype)


def normal_init(stddev: float = 0.02) -> Initializer:
    return lambda key, shape, dtype: (jax.random.normal(key, shape) * stddev).astype(dtype)


def fan_in_init(scale: float = 1.0, fan_in_axes: Sequence[int] = (-2,)) -> Initializer:
    """Truncated-normal-ish fan-in init (std = scale / sqrt(fan_in))."""

    def init(key, shape, dtype):
        fan_in = 1
        for ax in fan_in_axes:
            fan_in *= shape[ax]
        std = scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, shape) * std).astype(dtype)

    return init


def uniform_scale_init(scale: float = 1.0) -> Initializer:
    def init(key, shape, dtype):
        fan_in = shape[0] if shape else 1
        bound = scale * math.sqrt(3.0 / max(fan_in, 1))
        return jax.random.uniform(key, shape, minval=-bound, maxval=bound).astype(dtype)

    return init


@dataclasses.dataclass
class ParameterSpec:
    """Declarative description of one parameter."""

    shape: Tuple[int, ...]
    dtype: Any = jnp.float32
    initializer: Optional[Initializer] = None
    # Named-axis partition spec, e.g. ("data", "model"). None = replicated.
    mesh_axes: PartitionSpecLike = None
    # Weight-decay / clipping hints for the learner.
    weight_decay_scale: float = 1.0

    def initialize(self, key: jax.Array) -> jax.Array:
        init = self.initializer or normal_init()
        return init(key, tuple(self.shape), self.dtype)


def _stable_hash(name: str) -> int:
    return zlib.crc32(name.encode("utf-8"))



class BaseLayer(Module):
    """Module with parameters."""

    @config_class
    class Config(Module.Config):
        # Parameter dtype. Compute dtype follows inputs; params are cast at
        # use-sites if a global policy requires it.
        param_dtype: Any = jnp.float32
        # Optional override of every own-param partition spec (layers define
        # per-param defaults in _create_layer_parameter_specs).
        param_partition_spec: Optional[Any] = None
        # Mixed-precision policy (None = dtypes follow inputs / param_dtype).
        # Set on every layer in one pass by DtypePolicyModifier.
        dtype_policy: Optional[DtypePolicy] = None

    # GEMM layers opt into the fp8 module-boundary fake-quant (Linear sets
    # True); structural/norm/softmax layers keep full-precision boundaries,
    # which is what makes DtypePolicy.fp8 safe to set tree-wide.
    _fp8_boundary = False

    # --- parameter declaration (override in subclasses) ---------------------

    def _create_layer_parameter_specs(self) -> Dict[str, ParameterSpec]:
        return {}

    # --- kernel dispatch ----------------------------------------------------

    @property
    def kernel_config(self) -> KernelConfig:
        """The layer's :class:`KernelConfig` (kernel-calling layers declare a
        ``kernel`` field; others get the registry defaults). All kernel
        selection goes through this one sub-config — mesh rules rewrite it
        tree-wide via ``KernelModifier`` (paper §4.2), never layer code."""
        kcfg = getattr(self.config, "kernel", None)
        return kcfg if kcfg is not None else _DEFAULT_KERNEL_CONFIG

    # --- dtype policy -------------------------------------------------------

    def _resolve_param_spec_dtype(self, spec: ParameterSpec) -> ParameterSpec:
        """Applies cfg.param_dtype defaults + the policy's param_dtype.

        The policy only overrides specs that *follow* the layer param dtype;
        explicitly-pinned dtypes (fp32 islands like Mamba's ``A_log``) stay.
        """
        cfg = self.config
        if spec.dtype is None:
            spec = dataclasses.replace(spec, dtype=cfg.param_dtype)
        policy = cfg.dtype_policy
        if (policy is not None and policy.param_dtype is not None
                and spec.dtype == cfg.param_dtype
                and jnp.issubdtype(jnp.dtype(spec.dtype), jnp.floating)):
            spec = dataclasses.replace(spec, dtype=policy.param_dtype)
        return spec

    @property
    def compute_dtype(self) -> Optional[Any]:
        policy = self.config.dtype_policy
        return policy.compute_dtype if policy is not None else None

    def _fp8_config(self):
        """The active fp8 compute config, or None (off / layer opted out)."""
        policy = self.config.dtype_policy
        fp8 = getattr(policy, "fp8", None) if policy is not None else None
        return fp8 if (fp8 is not None and self._fp8_boundary) else None

    def _fp8_fake_quant(self, xs, fp8_cfg):
        """Delayed-scaling fake-quant of boundary inputs (+ amax rollup).

        Reads the layer's ``fp8_amax_history`` state (skips silently when
        absent — e.g. a checkpoint predating the policy) and, in training,
        emits the rolled history as a state update the train step folds
        back into the params.
        """
        from repro.quantization import fp8 as fp8_lib

        state = self.state
        history = state.get(fp8_lib.AMAX_HISTORY_KEY) \
            if isinstance(state, dict) else None
        if history is None:
            return xs
        out, amaxes = [], []
        for x in xs:
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                xq, amax = fp8_lib.boundary_fake_quant(
                    x, history, margin=fp8_cfg.margin)
                out.append(xq)
                amaxes.append(amax)
            else:
                out.append(x)
        if amaxes and self.is_training:
            amax = amaxes[0] if len(amaxes) == 1 else jnp.max(jnp.stack(amaxes))
            self.add_state_update(
                fp8_lib.AMAX_HISTORY_KEY,
                fp8_lib.roll_amax_history(history, amax))
        return tuple(out)

    def _to_compute(self, *xs):
        """Casts floating arrays to the policy compute dtype (module-boundary
        input cast; a no-op without a policy). Non-float leaves pass through.
        With ``DtypePolicy.fp8`` set, GEMM-boundary layers additionally
        fake-quantize the cast inputs to the e4m3 grid here."""
        dt = self.compute_dtype
        if dt is not None:
            def cast(x):
                if (hasattr(x, "dtype")
                        and jnp.issubdtype(x.dtype, jnp.floating)
                        and x.dtype != jnp.dtype(dt)):
                    return x.astype(dt)
                return x

            xs = tuple(cast(x) for x in xs)
        fp8_cfg = self._fp8_config()
        if fp8_cfg is not None:
            xs = self._fp8_fake_quant(xs, fp8_cfg)
        return xs[0] if len(xs) == 1 else tuple(xs)

    def _to_output(self, x: jax.Array) -> jax.Array:
        """Casts a head/model output to the policy output dtype (if set)."""
        policy = self.config.dtype_policy
        if policy is None or policy.output_dtype is None:
            return x
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(policy.output_dtype)
        return x

    # --- recursive spec/init (structural: no InvocationContext needed) ------

    def create_parameter_specs_recursively(self) -> Dict[str, Any]:
        specs: Dict[str, Any] = {}
        own = self._create_layer_parameter_specs()
        for name, spec in own.items():
            if self.config.param_partition_spec is not None:
                spec = dataclasses.replace(spec, mesh_axes=self.config.param_partition_spec)
            specs[name] = self._resolve_param_spec_dtype(spec)
        specs.update(self._fp8_parameter_specs())
        for child_name, child in self._children.items():
            if isinstance(child, BaseLayer):
                child_specs = child.create_parameter_specs_recursively()
                if child_specs:
                    specs[child_name] = child_specs
        return specs

    def _fp8_parameter_specs(self) -> Dict[str, ParameterSpec]:
        """The delayed-scaling amax history, when fp8 is active: a tiny
        replicated fp32 param (weight-decay exempt, dtype pinned — it
        bypasses the policy's param_dtype override on purpose)."""
        fp8 = self._fp8_config()
        if fp8 is None:
            return {}
        from repro.quantization.fp8 import AMAX_HISTORY_KEY

        return {AMAX_HISTORY_KEY: ParameterSpec(
            shape=(int(fp8.amax_history_len),), dtype=jnp.float32,
            initializer=zeros_init(), mesh_axes=None,
            weight_decay_scale=0.0)}

    def initialize_parameters_recursively(self, prng_key: jax.Array) -> Dict[str, Any]:
        params: Dict[str, Any] = {}
        own = self._create_layer_parameter_specs()
        for name, spec in own.items():
            spec = self._resolve_param_spec_dtype(spec)
            sub_key = jax.random.fold_in(prng_key, _stable_hash(name))
            params[name] = spec.initialize(sub_key)
        for name, spec in self._fp8_parameter_specs().items():
            sub_key = jax.random.fold_in(prng_key, _stable_hash(name))
            params[name] = spec.initialize(sub_key)
        for child_name, child in self._children.items():
            if isinstance(child, BaseLayer):
                sub_key = jax.random.fold_in(prng_key, _stable_hash(child_name))
                child_params = child.initialize_parameters_recursively(sub_key)
                if child_params:
                    params[child_name] = child_params
        return params

    # --- conveniences ---------------------------------------------------------

    def _shard(self, x: jax.Array, spec: PartitionSpecLike) -> jax.Array:
        return maybe_shard(x, spec)
