"""Tests for Module + InvocationContext (paper §4.3, Figure 3)."""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.config import REQUIRED, Required, config_class
from repro.core.module import Module, current_context, functional


class Leaf(Module):
    @config_class
    class Config(Module.Config):
        scale: float = 2.0

    def forward(self, x):
        # Summaries emitted without any ancestor knowing.
        self.add_summary("mean_in", jnp.mean(x))
        self.add_module_output("aux_loss", jnp.sum(x) * 0.0 + 1.0)
        return x * self.config.scale

    def stateful(self, x):
        w = self.state["w"]
        return x + w


class Parent(Module):
    @config_class
    class Config(Module.Config):
        child_a: Leaf.Config = Leaf.Config()
        child_b: Leaf.Config = Leaf.Config()

    def __init__(self, cfg, *, parent=None):
        super().__init__(cfg, parent=parent)
        self._add_child("a", cfg.child_a)
        self._add_child("b", cfg.child_b)

    def forward(self, x):
        return self.a(x) + self.b(x)

    def randomized(self, x):
        ka = self.a.rand_key()
        kb = self.b.rand_key()
        return ka, kb


class RandLeaf(Module):
    def rand_key(self, *args):
        return jax.random.normal(self.prng_key, (2,))


def test_instantiate_tree_names_and_paths():
    cfg = Parent.default_config().set(name="root")
    root = cfg.instantiate()
    assert root.name == "root"
    assert root.a.name == "a" and root.a.path == "root.a"
    assert set(root.children) == {"a", "b"}


def test_functional_forward_and_summaries():
    cfg = Parent.default_config().set(name="root")
    cfg.child_a.scale = 3.0
    cfg.child_b.scale = 5.0
    root = cfg.instantiate()
    x = jnp.ones((4,))
    out, col = functional(root, state={}, inputs=(x,), is_training=True,
                          prng_key=jax.random.PRNGKey(0))
    assert jnp.allclose(out, 8.0 * x)
    # Summaries collected under per-child paths; parent code never mentioned them.
    assert "a/mean_in" in col.summaries and "b/mean_in" in col.summaries
    assert set(k for k in col.module_outputs) == {"a/aux_loss", "b/aux_loss"}


def test_state_routing():
    class Holder(Module):
        @config_class
        class Config(Module.Config):
            leaf: Leaf.Config = Leaf.Config()

        def __init__(self, cfg, *, parent=None):
            super().__init__(cfg, parent=parent)
            self._add_child("leaf", cfg.leaf)

        def forward(self, x):
            return self.leaf.stateful(x)

    root = Holder.default_config().set(name="h").instantiate()
    state = {"leaf": {"w": jnp.full((3,), 10.0)}}
    out, _ = functional(root, state=state, inputs=(jnp.zeros(3),))
    assert jnp.allclose(out, 10.0)


def test_prng_split_deterministic_and_distinct():
    class R(Module):
        @config_class
        class Config(Module.Config):
            a: RandLeaf.Config = RandLeaf.Config()
            b: RandLeaf.Config = RandLeaf.Config()

        def __init__(self, cfg, *, parent=None):
            super().__init__(cfg, parent=parent)
            self._add_child("a", cfg.a)
            self._add_child("b", cfg.b)

        def forward(self):
            return self.a.rand_key(), self.b.rand_key()

    root = R.default_config().set(name="r").instantiate()
    (ka1, kb1), _ = functional(root, state={}, inputs=(), prng_key=jax.random.PRNGKey(7))
    (ka2, kb2), _ = functional(root, state={}, inputs=(), prng_key=jax.random.PRNGKey(7))
    assert jnp.allclose(ka1, ka2) and jnp.allclose(kb1, kb2), "deterministic"
    assert not jnp.allclose(ka1, kb1), "children get distinct keys"


def test_no_context_raises():
    leaf = Leaf.default_config().set(name="l").instantiate()
    with pytest.raises(RuntimeError, match="InvocationContext"):
        leaf(jnp.ones(2))


def test_context_accessible_from_plain_function():
    """Contexts reference modules, not vice-versa: 3rd-party code can reach them."""

    def third_party_helper():
        ctx = current_context()
        assert ctx is not None
        ctx.add_summary("from_outside", 42)
        return 0

    class M(Module):
        def forward(self, x):
            third_party_helper()
            return x

    m = M.default_config().set(name="m").instantiate()
    _, col = functional(m, state={}, inputs=(jnp.zeros(1),))
    assert col.summaries.get("from_outside") == 42


def test_jit_and_grad_compatible():
    class Lin(Module):
        def forward(self, x):
            return jnp.sum(self.state["w"] * x)

    m = Lin.default_config().set(name="lin").instantiate()

    def loss(state, x):
        out, _ = functional(m, state=state, inputs=(x,))
        return out

    g = jax.jit(jax.grad(loss))({"w": jnp.ones(3)}, jnp.arange(3.0))
    assert jnp.allclose(g["w"], jnp.arange(3.0))


def test_reentrant_same_module_method():
    class M(Module):
        def helper(self, x):
            return x + 1

        def forward(self, x):
            # Public method call on self should not push a duplicate frame.
            return self.helper(x) * 2

    m = M.default_config().set(name="m").instantiate()
    out, _ = functional(m, state={}, inputs=(jnp.array(1.0),))
    assert out == 4.0


def test_duplicate_child_rejected():
    class M(Module):
        @config_class
        class Config(Module.Config):
            leaf: Leaf.Config = Leaf.Config()

        def __init__(self, cfg, *, parent=None):
            super().__init__(cfg, parent=parent)
            self._add_child("x", cfg.leaf)
            self._add_child("x", cfg.leaf)

    with pytest.raises(ValueError, match="Duplicate child"):
        M.default_config().set(name="m").instantiate()


def test_invocations_name_their_ops():
    """Each invocation runs under a named scope: the module's name for
    ``forward``, ``<name>.<method>`` for another method, none for a
    re-entrant call on the same module. The compiled HLO keeps it."""

    class Scoped(Module):
        @config_class
        class Config(Module.Config):
            a: Leaf.Config = Leaf.Config()
            b: Leaf.Config = Leaf.Config()

        def __init__(self, cfg, *, parent=None):
            super().__init__(cfg, parent=parent)
            self._add_child("a", cfg.a)
            self._add_child("b", cfg.b)

        def helper(self, x):
            return jnp.sin(x)

        def forward(self, x):
            return self.helper(self.a(x)) + self.b.stateful(x)

    root = Scoped.default_config().set(name="root").instantiate()

    def f(state, x):
        return functional(root, state=state, inputs=(x,))[0]

    state = {"b": {"w": jnp.ones(3)}}
    text = jax.jit(f).lower(state, jnp.arange(3.0)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert "jit(f)/root/a/mul" in names
    assert "jit(f)/root/b.stateful/add" in names
    assert "jit(f)/root/sin" in names
    assert not any("helper" in n for n in names)
