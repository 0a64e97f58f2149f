"""Unit + property tests for the config system (paper §4.1)."""

import copy

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import registry
from repro.core.config import (
    REQUIRED,
    ConfigBase,
    Required,
    RequiredFieldMissingError,
    UnknownFieldError,
    config_class,
    config_for_class,
    config_for_function,
    config_to_dict,
    maybe_set,
    replace_config,
    visit_config,
)
from repro.layers import FeedForward
from repro.layers.moe import MoELayer
from repro.layers.rope import LinearScaledRotaryEmbedding, RotaryEmbedding


@config_class
class _InnerCfg(ConfigBase):
    dim: Required[int] = REQUIRED
    scale: float = 1.0


@config_class
class _OuterCfg(ConfigBase):
    inner: _InnerCfg = _InnerCfg()
    n: int = 3
    tag: str = "x"


def test_set_and_clone():
    cfg = _OuterCfg()
    cfg.inner.dim = 8
    clone = cfg.clone(n=5)
    assert clone.n == 5 and cfg.n == 3
    clone.inner.dim = 16
    assert cfg.inner.dim == 8, "clone must deep-copy children"


def test_unknown_field_raises_with_suggestion():
    cfg = _OuterCfg()
    with pytest.raises(UnknownFieldError) as e:
        cfg.nn = 4
    assert "n" in str(e.value)


def test_required_tracking():
    cfg = _InnerCfg()
    assert cfg.required_fields_missing() == ["dim"]
    cfg.dim = 4
    assert cfg.required_fields_missing() == []


def test_default_isolation():
    """Mutable defaults (child configs) must not be shared across instances."""
    a, b = _OuterCfg(), _OuterCfg()
    a.inner.scale = 9.0
    assert b.inner.scale == 1.0


def test_maybe_set_only_fills_unset():
    cfg = _InnerCfg()
    maybe_set(cfg, dim=4, scale=2.0, nonexistent=1)
    assert cfg.dim == 4
    assert cfg.scale == 1.0  # already set -> untouched


def test_config_for_function():
    def make(a, b=2, *, c=3):
        return a + b + c

    cfg = config_for_function(make)
    assert cfg.required_fields_missing() == ["a"]
    cfg.a = 1
    assert cfg.instantiate() == 6
    cfg.c = 10
    assert cfg.instantiate() == 13


def test_config_for_function_missing_required():
    def make(a):
        return a

    with pytest.raises(RequiredFieldMissingError):
        config_for_function(make).instantiate()


def test_config_for_class():
    class Thing:
        def __init__(self, x, y=2):
            self.val = x * y

    cfg = config_for_class(Thing).set(x=3)
    assert cfg.instantiate().val == 6


def test_nested_instantiation_through_function_config():
    def inner(v):
        return v * 2

    def outer(child, offset=1):
        return child + offset

    cfg = config_for_function(outer)
    cfg.child = config_for_function(inner).set(v=5)
    assert cfg.instantiate() == 11


def test_visit_config_paths():
    cfg = _OuterCfg()
    seen = []
    visit_config(cfg, lambda path, c: seen.append((path, type(c).__name__)))
    assert ("", "_OuterCfg") in seen
    assert ("inner", "_InnerCfg") in seen


@config_class
class _AltInnerCfg(ConfigBase):
    dim: Required[int] = REQUIRED
    extra: int = 7


def test_replace_config_by_type_propagates_interface_fields():
    cfg = _OuterCfg()
    cfg.inner.dim = 32
    n = replace_config(
        cfg,
        target=_InnerCfg,
        new_cfg=_AltInnerCfg(),
        propagate=("dim",),
    )
    assert n == 1
    assert isinstance(cfg.inner, _AltInnerCfg)
    assert cfg.inner.dim == 32, "interface field must carry over"


def test_replace_config_in_lists():
    @config_class
    class StackCfg(ConfigBase):
        layers: list = []

    cfg = StackCfg()
    cfg.layers = [_InnerCfg().set(dim=1), _AltInnerCfg().set(dim=2), _InnerCfg().set(dim=3)]
    n = replace_config(cfg, target=_InnerCfg, new_cfg=_AltInnerCfg(), propagate=("dim",))
    assert n == 2
    assert all(isinstance(l, _AltInnerCfg) for l in cfg.layers)
    assert [l.dim for l in cfg.layers] == [1, 2, 3]


def test_replace_config_with_predicate_and_factory():
    cfg = _OuterCfg()
    cfg.inner.dim = 8
    replace_config(
        cfg,
        target=lambda c: isinstance(c, _InnerCfg) and c.dim == 8,
        new_cfg=lambda old: _AltInnerCfg().set(dim=old.dim * 2),
        propagate=(),
    )
    assert cfg.inner.dim == 16


def test_config_to_dict_golden_stability():
    cfg = _OuterCfg()
    cfg.inner.dim = 4
    d1 = config_to_dict(cfg)
    d2 = config_to_dict(copy.deepcopy(cfg))
    assert d1 == d2
    assert d1["inner"]["dim"] == 4
    assert d1["__type__"].endswith("_OuterCfg")


# --------------------------- property tests --------------------------------


@st.composite
def outer_cfgs(draw):
    cfg = _OuterCfg()
    cfg.n = draw(st.integers(-100, 100))
    cfg.tag = draw(st.text(max_size=8))
    cfg.inner.dim = draw(st.integers(1, 4096))
    cfg.inner.scale = draw(st.floats(allow_nan=False, allow_infinity=False, width=32))
    return cfg


@given(outer_cfgs())
@settings(max_examples=50, deadline=None)
def test_clone_roundtrip_property(cfg):
    clone = cfg.clone()
    assert clone == cfg
    assert config_to_dict(clone) == config_to_dict(cfg)


@given(outer_cfgs(), st.integers(1, 64))
@settings(max_examples=50, deadline=None)
def test_replace_is_idempotent_property(cfg, dim):
    cfg.inner.dim = dim
    n1 = replace_config(cfg, target=_InnerCfg, new_cfg=_AltInnerCfg(), propagate=("dim",))
    n2 = replace_config(cfg, target=_InnerCfg, new_cfg=_AltInnerCfg(), propagate=("dim",))
    assert n1 == 1 and n2 == 0
    assert cfg.inner.dim == dim


# --- Paper Table 2: a feature variant is one replace_config call ------------
# Each snippet integrates its variant into any experiment config, with no
# change to layer code. Only configs are built; nothing is instantiated.


def _integrate_rope_variant(experiment_cfg):
    return replace_config(
        experiment_cfg,
        target=RotaryEmbedding,
        new_cfg=LinearScaledRotaryEmbedding.default_config().set(
            scaling_factor=4.0),
        propagate=("dim", "theta", "rotary_pct"),
    )


def _integrate_moe(experiment_cfg):
    return replace_config(
        experiment_cfg,
        target=FeedForward,
        new_cfg=MoELayer.default_config().set(num_experts=4, top_k=2),
        propagate=("input_dim", "hidden_dim"),
    )


def _sites(cfg, layer):
    found = []

    def visit(_, c):
        if isinstance(c, layer.Config):
            found.append(c)

    visit_config(cfg, visit)
    return found


# Archs whose configs hold the target layer: RoPE everywhere but jamba (no
# positional embedding), rwkv6 (recurrent) and hubert (convolutional); a
# dense FFN everywhere but mixtral (every FFN is MoE) and rwkv6 (channel mix).
_HAS_ROPE = {"qwen2-1.5b", "phi-3-vision-4.2b", "qwen1.5-4b", "mixtral-8x7b",
             "arctic-480b", "gemma2-27b", "internlm2-1.8b"}
_HAS_FFN = {"qwen2-1.5b", "phi-3-vision-4.2b", "qwen1.5-4b",
            "jamba-1.5-large-398b", "arctic-480b", "gemma2-27b",
            "hubert-xlarge", "internlm2-1.8b"}


@pytest.mark.parametrize("arch", registry.ASSIGNED_ARCHS)
def test_feature_integration_is_one_replace_config_call(arch):
    cfg = registry.get_spec(arch).make_model()
    for integrate, old, new, has in (
            (_integrate_rope_variant, RotaryEmbedding,
             LinearScaledRotaryEmbedding, arch in _HAS_ROPE),
            (_integrate_moe, FeedForward, MoELayer, arch in _HAS_FFN)):
        expected = len(_sites(cfg, old))
        assert (expected > 0) == has, (arch, old.__name__, expected)
        assert integrate(cfg) == expected, (arch, old.__name__)
        assert all(isinstance(c, new.Config) for c in _sites(cfg, old))
        config_to_dict(cfg)
