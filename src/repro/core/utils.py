"""Shared utilities: ambient mesh, sharding helpers, tree helpers, remat tags.

Config-based parallelism (paper §4.2) works by layers carrying partition
specs over *named axes*; at trace time the ambient mesh (set by the trainer /
dry-run launcher) resolves the names. Axis names absent from the active mesh
are dropped, so the same config runs on a 1-CPU test mesh and a 512-chip
production mesh unchanged.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "PartitionSpecLike",
    "make_mesh",
    "infer_mesh_shape",
    "set_mesh",
    "current_mesh",
    "resolve_spec",
    "maybe_shard",
    "named_sharding",
    "remat_name",
    "flatten_tree",
    "unflatten_tree",
    "tree_bytes",
    "tree_param_count",
    "cast_floats",
    "safe_zip_trees",
]

# A partition spec expressed as a tuple of axis names (or tuples of names, or
# None) — e.g. (("pod", "data"), None, "model").
PartitionSpecLike = Optional[Sequence[Union[str, Tuple[str, ...], None]]]


def make_mesh(shape, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types (GSPMD resolves shardings
    from the partition specs the layers carry). ``devices`` defaults to
    every device present."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices)


def infer_mesh_shape(shape, num_devices: int) -> Tuple[int, ...]:
    """Resolves a ``-1`` entry of a mesh shape to the devices the other axes
    leave over, so one rule fits every slice size (``(-1, 1)`` is a
    ``(1, 1)`` mesh on one chip and ``(4, 1)`` on four)."""
    shape = tuple(int(s) for s in shape)
    if shape.count(-1) > 1:
        raise ValueError(f"mesh shape {shape} has more than one -1")
    if -1 not in shape:
        return shape
    rest = math.prod(s for s in shape if s != -1)
    if num_devices % rest:
        raise ValueError(f"mesh shape {shape} does not divide "
                         f"{num_devices} devices")
    return tuple(num_devices // rest if s == -1 else s for s in shape)


class _MeshHolder(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None


_MESH = _MeshHolder()


@contextlib.contextmanager
def set_mesh(mesh: Optional[Mesh]):
    """Sets the ambient mesh used to resolve named partition specs."""
    prev = _MESH.mesh
    _MESH.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _MESH.mesh = prev


def current_mesh() -> Optional[Mesh]:
    return _MESH.mesh


def resolve_spec(spec: PartitionSpecLike, mesh: Optional[Mesh] = None) -> PartitionSpec:
    """Converts an axis-name tuple to a PartitionSpec valid for ``mesh``.

    Axis names not present in the mesh are dropped (replicated), which lets
    one config serve heterogeneous meshes — the paper's mesh-rule mechanism
    relies on this.
    """
    mesh = mesh or current_mesh()
    if spec is None:
        return PartitionSpec()
    names = set(mesh.axis_names) if mesh is not None else set()

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]
        return entry if entry in names else None

    return PartitionSpec(*[keep(e) for e in spec])


def named_sharding(spec: PartitionSpecLike, mesh: Optional[Mesh] = None,
                   *, memory_kind: Optional[str] = None) -> Optional[NamedSharding]:
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    kwargs = {}
    if memory_kind is not None:
        kwargs["memory_kind"] = memory_kind
    return NamedSharding(mesh, resolve_spec(spec, mesh), **kwargs)


def maybe_shard(x: jax.Array, spec: PartitionSpecLike) -> jax.Array:
    """with_sharding_constraint against the ambient mesh; no-op without one."""
    mesh = current_mesh()
    if mesh is None or spec is None or mesh.size == 1:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, resolve_spec(spec, mesh)))


def remat_name(x: Any, name: str) -> Any:
    """Tags an activation as a named remat point (paper's tagged remat)."""
    return checkpoint_name(x, name)


# ----------------------------- tree helpers --------------------------------


def flatten_tree(tree: Any, *, sep: str = "/", prefix: str = "") -> Dict[str, Any]:
    """Flattens a nested dict tree to {path: leaf}."""
    out: Dict[str, Any] = {}

    def rec(node, path):
        if isinstance(node, dict):
            for k in sorted(node):
                rec(node[k], f"{path}{sep}{k}" if path else str(k))
        else:
            out[path] = node

    rec(tree, prefix)
    return out


def unflatten_tree(flat: Dict[str, Any], *, sep: str = "/") -> Any:
    root: Dict[str, Any] = {}
    for path, leaf in flat.items():
        parts = path.split(sep)
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return root


def tree_bytes(tree: Any) -> int:
    leaves = jax.tree.leaves(tree)
    return sum(l.size * l.dtype.itemsize for l in leaves if hasattr(l, "size"))


def tree_param_count(tree: Any) -> int:
    return sum(l.size for l in jax.tree.leaves(tree) if hasattr(l, "size"))


def cast_floats(tree: Any, dtype) -> Any:
    def cast(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(cast, tree)


def safe_zip_trees(a: Any, b: Any):
    """Zips two trees with identical structure, yielding (leaf_a, leaf_b)."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb:
        raise ValueError(f"Tree structures differ: {ta} vs {tb}")
    return zip(la, lb)
