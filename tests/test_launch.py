"""Launch machinery tests (no heavy compiles): sharding-spec resolution, the
decode-state partition specs, the compile cache, and the mesh the v5e rule
builds from the attached devices."""

import jax
import pytest

from repro.configs import registry
from repro.core.utils import resolve_spec


def test_resolve_spec_drops_unknown_axes():
    from repro.core.utils import make_mesh

    mesh = make_mesh((1,), ("data",))
    spec = resolve_spec((("pod", "data"), None, "model"), mesh)
    assert spec == jax.sharding.PartitionSpec("data", None, None)


def test_state_partition_specs_match_state_structure():
    """Every arch's decode-state sharding tree must mirror init_states."""
    from repro.core.module import functional

    for arch in ["qwen2-1.5b", "jamba-1.5-large-398b", "rwkv6-7b",
                 "mixtral-8x7b"]:
        spec = registry.get_spec(arch)
        model = spec.make_smoke().instantiate()
        specs = model.state_partition_specs()
        cache, _ = functional(model, state={}, inputs=(2, 16),
                              method="init_states")

        def paths(tree):
            flat = jax.tree_util.tree_flatten_with_path(
                tree, is_leaf=lambda x: isinstance(x, tuple) or x is None)[0]
            return {jax.tree_util.keystr(p) for p, _ in flat}

        assert paths(specs) == paths(cache), arch


# ------------------------------ chip bring-up --------------------------------


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and the helper
    sets no other directory."""
    from repro.core import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch):
    """Unset: the cache goes to <checkout>/.jax_cache, the same path on
    every run (the directory is part of the cache key)."""
    import pathlib

    from repro.core import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure_compile_cache()
    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert path == str(checkout / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.configure_compile_cache() == path


@pytest.mark.parametrize("shape,devices,expect", [
    ((-1, 1), 1, (1, 1)),
    ((-1, 1), 4, (4, 1)),
    ((2, -1), 4, (2, 2)),
    ((16, 16), 4, (16, 16)),  # explicit shapes pass through unchanged
    ((-1, 3), 4, ValueError),
    ((-1, -1), 4, ValueError),
])
def test_infer_mesh_shape(shape, devices, expect):
    from repro.core.utils import infer_mesh_shape

    if expect is ValueError:
        with pytest.raises(ValueError):
            infer_mesh_shape(shape, devices)
    else:
        assert infer_mesh_shape(shape, devices) == expect


def test_v5e_rule_mesh_fits_attached_devices():
    """The v5e rule takes every chip present on the data axis; the trainer
    builds that mesh from the devices it sees (here: one CPU device)."""
    from repro.launch.train import attached_instance_type, build_trainer_config

    assert attached_instance_type() == "cpu-local"
    cfg = build_trainer_config("qwen2-1.5b", full=False, steps=1, batch=2,
                               seq=8, lr=1e-3, instance_type="tpu-v5e-4")
    assert cfg.mesh_shape == (-1, 1)
    assert cfg.opt_state_sharding == "zero1"
    mesh = cfg.instantiate().build_mesh()
    assert dict(mesh.shape) == {"data": len(jax.devices()), "model": 1}


def test_attached_instance_type_rejects_unknown_accelerator(monkeypatch):
    import types

    from repro.launch import train

    fake = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")] * 4
    monkeypatch.setattr(jax, "devices", lambda: fake)
    assert train.attached_instance_type() == "tpu-v5e-4"
    fake = [types.SimpleNamespace(platform="tpu", device_kind="TPU v9")]
    monkeypatch.setattr(jax, "devices", lambda: fake)
    with pytest.raises(ValueError, match="TPU v9"):
        train.attached_instance_type()
