"""Module system with InvocationContext (AXLearn §4.3, Figure 3).

JAX programs must be purely functional, but neural nets are stateful. Rather
than forcing users to thread params/PRNG/summaries through every call, an
``InvocationContext`` is transparently pushed when a parent module invokes a
child, which:

  * routes the child's parameter subtree from the parent state,
  * splits the PRNG key deterministically by child name,
  * gives the child a place to emit summaries and module outputs (e.g. MoE
    load-balance losses) that are collected up the stack *without any
    ancestor layer knowing about them*,
  * opens a ``jax.named_scope`` named after the module (``<name>.<method>``
    for a method other than ``forward``), so every op the invocation stages
    carries the module path in its HLO ``op_name`` and in the device trace.

The root entrypoint is :func:`functional` (the analogue of AXLearn's ``F``),
which runs a module method under a fresh root context and returns
``(outputs, OutputCollection)`` — a pure function suitable for jit/grad.

Contexts reference modules but not vice-versa, so arbitrary (even 3rd-party)
code can reach :func:`current_context` without holding a module reference.
"""

from __future__ import annotations

import dataclasses
import inspect
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax

from repro.core.config import (
    REQUIRED,
    ConfigBase,
    InstantiableConfig,
    Required,
    config_class,
)

__all__ = [
    "Module",
    "InvocationContext",
    "OutputCollection",
    "current_context",
    "functional",
    "new_output_collection",
    "child_context",
]


def _stable_hash(name: str) -> int:
    """Deterministic across processes (unlike Python's hash)."""
    return zlib.crc32(name.encode("utf-8"))


@dataclasses.dataclass
class OutputCollection:
    """Side outputs emitted during an invocation.

    ``summaries``: scalar/tensor diagnostics keyed by module path.
    ``module_outputs``: auxiliary computation results (e.g. ``aux_loss``)
        keyed by module path; the learner aggregates matching keys.
    ``state_updates``: updated stateful tensors (e.g. KV caches) keyed by
        module path.
    """

    summaries: Dict[str, Any] = dataclasses.field(default_factory=dict)
    module_outputs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    state_updates: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def update(self, other: "OutputCollection"):
        self.summaries.update(other.summaries)
        self.module_outputs.update(other.module_outputs)
        self.state_updates.update(other.state_updates)


def new_output_collection() -> OutputCollection:
    return OutputCollection()


class _ContextStack(threading.local):
    def __init__(self):
        self.stack: List["InvocationContext"] = []


_CONTEXT_STACK = _ContextStack()


def no_context(fn):
    """Marks a public Module method as structural: callable without an
    InvocationContext (it must not touch traced state/PRNG)."""
    fn._no_ctx = True
    return fn


def current_context() -> Optional["InvocationContext"]:
    stack = _CONTEXT_STACK.stack
    return stack[-1] if stack else None


@dataclasses.dataclass
class InvocationContext:
    """One frame of the invocation stack (paper Figure 3)."""

    module: "Module"
    state: Any
    path: str
    is_training: bool
    prng_key: Optional[jax.Array]
    output_collection: OutputCollection
    method: str = "forward"
    _scope: Any = dataclasses.field(default=None, init=False, repr=False)

    # --- stack management ---------------------------------------------------

    def __enter__(self) -> "InvocationContext":
        # Naming only: the ops keep the scope in their ``op_name`` (and XLA
        # may name an instruction after it); the computation is unchanged.
        name = self.module.name
        if self.method != "forward":
            name = f"{name}.{self.method}"
        self._scope = jax.named_scope(name)
        self._scope.__enter__()
        _CONTEXT_STACK.stack.append(self)
        return self

    def __exit__(self, *exc):
        popped = _CONTEXT_STACK.stack.pop()
        assert popped is self
        self._scope.__exit__(*exc)
        self._scope = None
        return False

    def child(
        self,
        module: "Module",
        *,
        state: Any = None,
        prng_key: Optional[jax.Array] = None,
        output_collection: Optional[OutputCollection] = None,
        method: str = "forward",
    ) -> "InvocationContext":
        """Creates the context for invoking ``module.<method>`` as a child of
        this one."""
        name = module.name
        if state is None:
            state = self.state.get(name, {}) if isinstance(self.state, dict) else {}
        if prng_key is None and self.prng_key is not None:
            prng_key = jax.random.fold_in(self.prng_key, _stable_hash(name))
        return InvocationContext(
            module=module,
            state=state,
            path=f"{self.path}/{name}" if self.path else name,
            is_training=self.is_training,
            prng_key=prng_key,
            # Shared root collection: children write under their own path, so
            # no merge step is needed and ancestors stay oblivious.
            output_collection=(
                output_collection if output_collection is not None else self.output_collection
            ),
            method=method,
        )

    # --- side-output API ----------------------------------------------------

    def add_summary(self, name: str, value: Any):
        self.output_collection.summaries[f"{self.path}/{name}" if self.path else name] = value

    def add_module_output(self, name: str, value: Any):
        self.output_collection.module_outputs[f"{self.path}/{name}" if self.path else name] = value

    def add_state_update(self, name: str, value: Any):
        self.output_collection.state_updates[f"{self.path}/{name}" if self.path else name] = value


def child_context(module: "Module", **overrides) -> InvocationContext:
    ctx = current_context()
    if ctx is None:
        raise RuntimeError(
            "No InvocationContext. Wrap the call with repro.core.module.functional()."
        )
    return ctx.child(module, **overrides)


class _AutoContextMeta(type):
    """Wraps public methods so child invocations push contexts transparently.

    User layer code therefore looks imperative (``self.ffn(x)``) while staying
    functional — the paper's key usability claim.
    """

    _NO_WRAP = {
        "default_config",
        "__init__",
        "__init_subclass__",
        # Structural methods: operate on configs/specs, not on traced state.
        "initialize_parameters_recursively",
        "create_parameter_specs_recursively",
    }

    def __new__(mcs, name, bases, namespace):
        for attr, value in list(namespace.items()):
            if attr.startswith("_") or attr in mcs._NO_WRAP:
                continue
            if inspect.isfunction(value):
                namespace[attr] = mcs._wrap(value)
        return super().__new__(mcs, name, bases, namespace)

    @staticmethod
    def _wrap(fn):
        if getattr(fn, "_no_ctx", False):
            return fn
        if getattr(fn, "_ctx_wrapped", False):
            return fn

        def wrapped(self, *args, **kwargs):
            ctx = current_context()
            if ctx is None:
                raise RuntimeError(
                    f"Calling {type(self).__name__}.{fn.__name__} outside an "
                    "InvocationContext; use repro.core.module.functional()."
                )
            if ctx.module is self:
                # Re-entrant call on the same module (e.g. forward calling a
                # sibling public method): stay in the current frame.
                return fn(self, *args, **kwargs)
            with ctx.child(self, method=fn.__name__):
                return fn(self, *args, **kwargs)

        wrapped._ctx_wrapped = True
        wrapped.__name__ = fn.__name__
        wrapped.__qualname__ = getattr(fn, "__qualname__", fn.__name__)
        wrapped.__doc__ = fn.__doc__
        wrapped._original = fn
        return wrapped


class Module(metaclass=_AutoContextMeta):
    """Base class of every component: layers, models, trainers, inputs.

    A Module is defined by its nested ``Config`` (strictly encapsulating its
    children's configs) and builds its children in ``__init__`` via
    ``_add_child``. Modules hold *no tensors* — parameters live in the state
    tree threaded by InvocationContexts.
    """

    @config_class
    class Config(InstantiableConfig):
        name: Optional[str] = None

        def instantiate(self, *, parent: Optional["Module"] = None) -> "Module":
            missing = self.required_fields_missing()
            if missing:
                raise ValueError(
                    f"Cannot instantiate {type(self).__qualname__}: required "
                    f"fields not set: {missing}"
                )
            module_cls = getattr(type(self), "_module_cls", None)
            assert module_cls is not None, type(self)
            return module_cls(self, parent=parent)

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        # Bind the innermost Config class defined on (or inherited by) cls.
        cfg_cls = cls.__dict__.get("Config")
        if cfg_cls is not None:
            cfg_cls = config_class(cfg_cls)  # idempotent; collects declared fields
            cfg_cls._module_cls = cls
            cls.Config = cfg_cls
        else:
            # Subclass without its own Config: generate one inheriting the
            # parent's so default_config() instantiates the right class.
            parent_cfg = cls.Config

            cfg_cls = config_class(
                type("Config", (parent_cfg,), {"_module_cls": cls, "__qualname__": f"{cls.__qualname__}.Config"})
            )
            cls.Config = cfg_cls

    @classmethod
    def default_config(cls) -> "Module.Config":
        return cls.Config()

    def __init__(self, cfg: "Module.Config", *, parent: Optional["Module"] = None):
        self._config = cfg.clone()
        self._parent = parent
        self._children: Dict[str, "Module"] = {}
        if cfg.name is None:
            self._config.set(name=type(self).__name__.lower())

    # --- tree structure -----------------------------------------------------

    @property
    def config(self) -> "Module.Config":
        return self._config

    @property
    def name(self) -> str:
        return self._config.name

    @property
    def children(self) -> Dict[str, "Module"]:
        return dict(self._children)

    @property
    def path(self) -> str:
        if self._parent is None:
            return self.name
        return f"{self._parent.path}.{self.name}"

    def _add_child(self, name: str, child_cfg: InstantiableConfig) -> "Module":
        if name in self._children:
            raise ValueError(f"Duplicate child {name!r} in {self.path}.")
        child_cfg = child_cfg.clone()
        if "name" in child_cfg.keys():
            child_cfg.set(name=name)
        child = child_cfg.instantiate(parent=self)
        self._children[name] = child
        # Expose as attribute for the imperative style: self.ffn(x).
        object.__setattr__(self, name, child)
        return child

    # --- context plumbing (private: not auto-wrapped) ------------------------

    @property
    def _ctx(self) -> InvocationContext:
        ctx = current_context()
        if ctx is None or ctx.module is not self:
            raise RuntimeError(
                f"{self.path}: no active InvocationContext for this module."
            )
        return ctx

    @property
    def state(self) -> Any:
        return self._ctx.state

    @property
    def is_training(self) -> bool:
        return self._ctx.is_training

    @property
    def prng_key(self) -> jax.Array:
        key = self._ctx.prng_key
        if key is None:
            raise RuntimeError(f"{self.path}: no PRNG key available (inference mode?).")
        return key

    def parameters(self) -> Any:
        """The module's parameter subtree from the active context."""
        return self._ctx.state

    def add_summary(self, name: str, value: Any):
        self._ctx.add_summary(name, value)

    def add_module_output(self, name: str, value: Any):
        self._ctx.add_module_output(name, value)

    def add_state_update(self, name: str, value: Any):
        self._ctx.add_state_update(name, value)

    # --- default interface ----------------------------------------------------

    def forward(self, *args, **kwargs):
        raise NotImplementedError(type(self))

    def __call__(self, *args, **kwargs):
        ctx = current_context()
        if ctx is None:
            raise RuntimeError(
                f"Calling {type(self).__name__} outside an InvocationContext; "
                "use repro.core.module.functional()."
            )
        if ctx.module is self:
            return type(self).forward._original(self, *args, **kwargs) if hasattr(
                type(self).forward, "_original"
            ) else type(self).forward(self, *args, **kwargs)
        with ctx.child(self):
            fwd = type(self).forward
            fwd = getattr(fwd, "_original", fwd)
            return fwd(self, *args, **kwargs)

    def __repr__(self):
        return f"{type(self).__name__}({self.path})"


# Bind the base config to the base module class.
Module.Config._module_cls = Module


def functional(
    module: Module,
    *,
    state: Any,
    inputs: Union[Tuple, Dict[str, Any]],
    prng_key: Optional[jax.Array] = None,
    is_training: bool = False,
    method: str = "forward",
) -> Tuple[Any, OutputCollection]:
    """Purely-functional invocation of a module method (AXLearn's ``F``).

    Returns ``(outputs, output_collection)``. Safe to wrap in jit/grad.
    """
    collection = new_output_collection()
    ctx = InvocationContext(
        module=module,
        state=state,
        path="",
        is_training=is_training,
        prng_key=prng_key,
        output_collection=collection,
        method=method,
    )
    fn = getattr(type(module), method)
    fn = getattr(fn, "_original", fn)
    with ctx:
        if isinstance(inputs, dict):
            outputs = fn(module, **inputs)
        else:
            outputs = fn(module, *inputs)
    return outputs, collection
