"""Composable SPMD train-step builder (paper §3–§5).

``SpmdTrainer.make_train_step`` used to be one monolithic closure; the
pieces now compose so launchers can build custom steps from the
same parts the trainer uses:

  * :func:`make_loss_fn` — model forward + aux-loss aggregation.
  * :func:`make_grad_fn` — value_and_grad with microbatched gradient
    accumulation that accumulates in a configurable grad dtype (the policy's
    ``grad_dtype``) instead of hardcoded fp32 buffers, validates batch
    divisibility, and passes non-splittable batch entries (shared position
    arrays, scalars) through to every microbatch instead of crashing.
  * :func:`build_train_step` — grads -> learner update, with optional
    ZeRO-1 sharding constraints threaded to the learner.
  * :func:`zero1_partition_spec` — optimizer-state partitioning along the
    data axes (ZeRO-1 / optimizer-state sharding a la SageMaker MP): each
    param-shaped optimizer leaf gets one extra dim sharded over the data
    axes, shrinking per-device moment bytes ~Nx on an N-way data mesh.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.module import functional
from repro.core.utils import maybe_shard, resolve_spec
from repro.layers.base import ParameterSpec
from repro.trainer.learner import aggregate_aux_losses

__all__ = [
    "scalar_summaries",
    "make_loss_fn",
    "make_grad_fn",
    "apply_state_updates",
    "build_train_step",
    "zero1_partition_spec",
    "constrain_tree",
    "slice_microbatch",
    "combine_microbatch_grads",
    "canonical_mean",
]

TrainState = Dict[str, Any]


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer-state partitioning
# ---------------------------------------------------------------------------


def zero1_partition_spec(spec: ParameterSpec, mesh,
                         axes: Sequence[str] = ("pod", "data")) -> PartitionSpec:
    """ZeRO-1 sharding for one param-shaped optimizer-state leaf.

    Starts from the param's own partition spec and additionally shards the
    first dimension that is (a) not already sharded and (b) divisible by the
    total data-axis size, over the data axes. Falls back to the param spec
    when no dimension divides (tiny scalars/biases stay as-is — they are a
    rounding error of optimizer HBM).
    """
    base = tuple(spec.mesh_axes) if spec.mesh_axes is not None else ()
    base = base + (None,) * (len(spec.shape) - len(base))
    # Resolve against the mesh FIRST: an axis name absent from the mesh (or
    # dropped by resolve) means the dim is really replicated and fair game.
    resolved = tuple(resolve_spec(base, mesh))
    resolved = resolved + (None,) * (len(spec.shape) - len(resolved))
    # Only axes the param does not already use anywhere are addable — a
    # PartitionSpec must not name one mesh axis twice (FSDP-style params
    # that already shard over "data" need no ZeRO-1 help: their moments
    # inherit that sharding).
    used = set()
    for entry in resolved:
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if a is not None:
                used.add(a)
    addable = [a for a in axes
               if mesh is not None and a in mesh.axis_names and a not in used]
    n = 1
    for a in addable:
        n *= mesh.shape[a]
    if n <= 1:
        return PartitionSpec(*resolved)
    for d, (dim, entry) in enumerate(zip(spec.shape, resolved)):
        if entry is None and dim % n == 0:
            extra = tuple(addable) if len(addable) > 1 else addable[0]
            new = resolved[:d] + (extra,) + resolved[d + 1:]
            return PartitionSpec(*new)
    return PartitionSpec(*resolved)


def constrain_tree(tree: Any, specs: Optional[Any]) -> Any:
    """with_sharding_constraint over a matching tree of PartitionSpecs."""
    if specs is None:
        return tree
    return jax.tree.map(lambda x, s: maybe_shard(x, s), tree, specs)


# ---------------------------------------------------------------------------
# Loss / grads
# ---------------------------------------------------------------------------


def scalar_summaries(col) -> Dict[str, Any]:
    """The exportable slice of an ``OutputCollection``: scalar summaries
    (loss/accuracy, MoE load-balance stats and counters, per-layer norms)
    keyed by module path, a layer's scalar stacked into one value per layer
    by a scanned ``Repeat``. Larger summaries (activation histograms etc.)
    stay in the collection for callers that want them — routing tensors out
    of every step would bloat the jitted step's outputs for no telemetry
    gain."""
    out = {}
    for k, v in col.summaries.items():
        if isinstance(v, (int, float)) or getattr(v, "ndim", None) in (0, 1):
            out[k] = v
    return out


def make_loss_fn(model, *, aux_loss_weight: float = 1.0,
                 aux_loss_pattern: str = r".*/aux_loss$") -> Callable:
    """(params, batch, step_key) -> (total_loss, {"loss", "aux_loss",
    "summaries"}).

    ``summaries`` routes every scalar ``add_summary`` value out of the
    jitted step (they used to be collected into the OutputCollection and
    dropped) so the trainer can export them through the metrics registry.
    """

    def loss_fn(params, batch, step_key):
        (loss, _aux), col = functional(
            model, state=params, inputs=(batch,), prng_key=step_key,
            is_training=True)
        aux_total = aggregate_aux_losses(col, aux_loss_pattern)
        total = loss + aux_loss_weight * aux_total
        # State updates (fp8 amax histories) ride out of the collection
        # keyed by module path; build_train_step folds them back into the
        # params after the optimizer update.
        return total, {"loss": loss, "aux_loss": aux_total,
                       "summaries": scalar_summaries(col),
                       "state_updates": dict(col.state_updates)}

    return loss_fn


def _split_batch(batch: Dict[str, Any], accum: int):
    """Splits array entries with the global batch dim into ``accum``
    microbatches; everything else (shared position arrays, scalars,
    non-arrays) is passed through to every microbatch unchanged."""
    arrays = {k: v for k, v in batch.items()
              if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1}
    if not arrays:
        raise ValueError(
            "grad_accum_steps > 1 requires at least one array batch entry "
            f"with a leading batch dimension; got keys {sorted(batch)}")
    # The global batch dim: taken from 'labels'/'input_ids' when present so
    # sequence-shaped extras can't masquerade as the batch axis.
    for anchor in ("labels", "input_ids"):
        if anchor in arrays:
            B = arrays[anchor].shape[0]
            break
    else:
        B = arrays[sorted(arrays)[0]].shape[0]
    if B % accum != 0:
        raise ValueError(
            f"Global batch size {B} is not divisible by grad_accum_steps="
            f"{accum}; pick a batch size that is a multiple of the "
            f"accumulation steps (microbatch = batch/steps).")
    split, static = {}, {}
    for k, v in batch.items():
        if k in arrays and v.shape[0] == B:
            split[k] = v.reshape((accum, B // accum) + v.shape[1:])
        else:
            static[k] = v
    return split, static


def make_grad_fn(loss_fn: Callable, *, grad_accum_steps: int = 1,
                 grad_dtype: Optional[Any] = None) -> Callable:
    """(params, batch, step_key) -> (total, parts, grads).

    With ``grad_accum_steps > 1`` the batch is split into microbatches and
    gradients accumulate in ``grad_dtype`` (None -> each param's dtype, i.e.
    fp32 for master-weight training) across a ``lax.scan``.
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    accum = grad_accum_steps

    def compute_grads(params, batch, step_key):
        if accum <= 1:
            (total, parts), grads = grad_fn(params, batch, step_key)
            return total, parts, grads

        split, static = _split_batch(batch, accum)

        def microbatch(acc_grads, mb):
            mb_key = jax.random.fold_in(step_key, mb["_idx"])
            mb_batch = {k: v for k, v in mb.items() if k != "_idx"}
            mb_batch.update(static)
            (total, parts), grads = grad_fn(params, mb_batch, mb_key)
            acc_grads = jax.tree.map(
                lambda a, g: a + g.astype(a.dtype), acc_grads, grads)
            # Scalar metrics (incl. the routed summaries subtree) ride as
            # scan outputs and are averaged over microbatches below.
            return acc_grads, {"_total": total, **parts}

        split["_idx"] = jnp.arange(accum)
        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, grad_dtype or p.dtype), params)
        grads, parts_stack = jax.lax.scan(microbatch, zero_grads, split)
        inv = 1.0 / accum
        grads = jax.tree.map(lambda g: g * jnp.asarray(inv, g.dtype), grads)
        # State updates are amax-semantics (fp8 histories): microbatches
        # combine by elementwise max, not the scalar-metric mean — slot
        # [0] becomes the step's true amax, the rolled-forward tail is
        # identical across microbatches so max is the identity there.
        state_updates = parts_stack.pop("state_updates", {})
        parts = jax.tree.map(lambda x: jnp.mean(x, axis=0), parts_stack)
        if state_updates:
            parts["state_updates"] = jax.tree.map(
                lambda x: jnp.max(x, axis=0), state_updates)
        else:
            parts["state_updates"] = {}
        total = parts.pop("_total")
        return total, parts, grads

    return compute_grads


# ---------------------------------------------------------------------------
# Elastic (world-size-invariant) microbatch decomposition
# ---------------------------------------------------------------------------
#
# The elastic trainer decomposes every global batch into a FIXED number of
# canonical microbatches G, independent of how many processes share the work
# (each process computes a contiguous block of them with the same jitted
# per-microbatch program). Gradients are then combined on the host in
# canonical microbatch order with left-associative float32 arithmetic — the
# same programs, the same data, and the same addition order at every world
# size means bitwise-identical optimizer updates whether the job runs on 1
# process or N, which is what lets a resharded resume reproduce the
# uninterrupted loss curve exactly.


def slice_microbatch(batch: Dict[str, Any], mb_index: int,
                     num_microbatches: int) -> Dict[str, Any]:
    """Canonical microbatch ``mb_index`` of the GLOBAL batch: the contiguous
    row block ``[m*B/G, (m+1)*B/G)`` of every batch-dim entry; non-batch
    entries (shared position arrays, scalars) pass through unchanged."""
    arrays = {k: v for k, v in batch.items()
              if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1}
    for anchor in ("labels", "input_ids"):
        if anchor in arrays:
            B = arrays[anchor].shape[0]
            break
    else:
        B = arrays[sorted(arrays)[0]].shape[0]
    if B % num_microbatches != 0:
        raise ValueError(
            f"Global batch size {B} is not divisible by grad_microbatches="
            f"{num_microbatches}")
    sz = B // num_microbatches
    lo = mb_index * sz
    return {k: (v[lo:lo + sz] if k in arrays and v.shape[0] == B else v)
            for k, v in batch.items()}


def canonical_mean(values: Sequence[Any]) -> np.ndarray:
    """Left-associative float32 mean in the given (canonical) order. Every
    process must fold contributions through this exact reduction for the
    result to be bitwise world-size-invariant."""
    acc = np.zeros_like(np.asarray(values[0], np.float32))
    for v in values:
        acc = (acc + np.asarray(v, np.float32)).astype(np.float32)
    return (acc * np.float32(1.0 / len(values))).astype(np.float32)


def combine_microbatch_grads(per_mb_leaves: Sequence[Sequence[Any]],
                             treedef) -> Any:
    """Host-side mean of per-microbatch gradient contributions.

    ``per_mb_leaves[m]`` is microbatch ``m``'s flat leaf list (float32
    numpy arrays, in ``jax.tree_util.tree_flatten`` order). Accumulation is
    leaf-wise, left-associative over microbatches in canonical order — see
    :func:`canonical_mean` for why the order is load-bearing.
    """
    G = len(per_mb_leaves)
    accs = [np.array(leaf, dtype=np.float32, copy=True)
            for leaf in per_mb_leaves[0]]
    for leaves in per_mb_leaves[1:]:
        for i, leaf in enumerate(leaves):
            accs[i] += np.asarray(leaf, np.float32)
    inv = np.float32(1.0 / G)
    for i in range(len(accs)):
        accs[i] *= inv
    return jax.tree_util.tree_unflatten(treedef, accs)


# ---------------------------------------------------------------------------
# Full step
# ---------------------------------------------------------------------------


def apply_state_updates(params: Dict[str, Any],
                        updates: Dict[str, Any]) -> Dict[str, Any]:
    """Folds OutputCollection state updates back into a params tree.

    ``updates`` is keyed by "/"-joined module path (the InvocationContext
    naming scheme), which maps exactly onto params-dict nesting — Repeat
    re-emits scan-stacked updates under its ``layer`` subtree so stacked
    layouts address the same way. Copy-on-write: only the dicts along each
    updated path are rebuilt. Unknown paths raise (an update implies the
    leaf existed in the state the forward ran with).
    """

    def set_path(node, keys, value):
        key = keys[0]
        if not isinstance(node, dict) or key not in node:
            raise KeyError(
                f"state update path {'/'.join(keys)!r} not found in params")
        out = dict(node)
        if len(keys) == 1:
            old = node[key]
            out[key] = value.astype(old.dtype) \
                if hasattr(value, "astype") else value
        else:
            out[key] = set_path(node[key], keys[1:], value)
        return out

    for path, value in updates.items():
        params = set_path(params, path.split("/"), value)
    return params


def build_train_step(
    model,
    learner,
    *,
    aux_loss_weight: float = 1.0,
    aux_loss_pattern: str = r".*/aux_loss$",
    grad_accum_steps: int = 1,
    grad_dtype: Optional[Any] = None,
    update_partition_specs: Optional[Any] = None,  # ZeRO-1 specs per param
    param_partition_specs: Optional[Any] = None,
) -> Callable[[TrainState, Dict[str, Any]], Tuple[TrainState, Dict[str, Any]]]:
    """Composes loss -> grads -> update into the jittable train step.

    With ``update_partition_specs`` set (ZeRO-1), gradients are constrained
    to the data-sharded optimizer layout before the optimizer update (GSPMD
    lowers the psum into a reduce-scatter) and the applied params are
    constrained back to ``param_partition_specs`` afterwards — no explicit
    collectives anywhere, sharding constraints only (paper §4.2).
    """
    from repro.trainer.optimizers import global_norm

    loss_fn = make_loss_fn(model, aux_loss_weight=aux_loss_weight,
                           aux_loss_pattern=aux_loss_pattern)
    compute_grads = make_grad_fn(loss_fn, grad_accum_steps=grad_accum_steps,
                                 grad_dtype=grad_dtype)

    def train_step(state: TrainState, batch: Dict[str, Any]):
        step_key = jax.random.fold_in(state["prng_key"], state["step"])
        total, parts, grads = compute_grads(state["params"], batch, step_key)
        state_updates = parts.pop("state_updates", None)
        # The learner has no module scope (``apply_updates`` is structural),
        # so the update gets one of its own. The norm telemetry shares it:
        # XLA fuses those reductions into the update's fusions.
        with jax.named_scope("learner"):
            new_params, new_opt = learner.apply_updates(
                grads, state["opt_state"], state["params"],
                update_partition_specs=update_partition_specs,
                param_partition_specs=param_partition_specs)
            if state_updates:
                # Forward-pass state (fp8 amax histories) overwrites the
                # optimizer's view of those leaves — they are carried as
                # params only so they checkpoint/shard like everything else.
                new_params = apply_state_updates(new_params, state_updates)
            # Norm telemetry: grad/param/update norms are the first things a
            # diverging run's operator looks at, so they come out of every
            # step (computed inside jit — no extra dispatches, no retraces).
            update = jax.tree.map(
                lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
                new_params, state["params"])
            norms = {"grad_norm": global_norm(grads),
                     "param_norm": global_norm(new_params),
                     "update_norm": global_norm(update)}
        metrics = {"total_loss": total, **norms, **parts}
        new_state = {
            "step": state["step"] + 1,
            "prng_key": state["prng_key"],
            "params": new_params,
            "opt_state": new_opt,
        }
        return new_state, metrics

    return train_step
