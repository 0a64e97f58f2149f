"""Launch/analysis machinery tests (no heavy compiles — the real dry-run
artifacts live in experiments/dryrun; these validate the components)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.utils import resolve_spec, set_mesh
from repro.launch.analysis import (
    _type_bytes,
    parse_collectives_dedup,
    roofline_terms,
)

HLO_SAMPLE = """
  %ag = bf16[16,1024]{1,0} all-gather(%p0), replica_groups={...}
  %ar.1 = f32[32,32]{1,0} all-reduce(%x), to_apply=%add
  %ars = f32[32,32]{1,0} all-reduce-start(%x), to_apply=%add
  %ard = f32[32,32]{1,0} all-reduce-done(%ars)
  %a2a = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-to-all(%y, %z)
  %cp = u32[4]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %rs = f32[16]{0} reduce-scatter(%v), to_apply=%add
"""


def test_type_bytes():
    assert _type_bytes("bf16[16,1024]{1,0}") == 16 * 1024 * 2
    assert _type_bytes("f32[]") == 4  # scalar
    assert _type_bytes("(bf16[8,8], bf16[8,8])") == 2 * 64 * 2


def test_parse_collectives_dedup():
    out = parse_collectives_dedup(HLO_SAMPLE)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 16 * 1024 * 2
    # start counted once, done skipped, plain one counted.
    assert out["all-reduce"]["count"] == 2
    assert out["all-to-all"]["bytes"] == 2 * 64 * 2
    assert out["collective-permute"]["count"] == 1
    assert out["reduce-scatter"]["count"] == 1


def test_roofline_terms_dominance():
    rep = roofline_terms(
        cost={"flops": 197e12, "bytes accessed": 819e9 * 2},
        hlo_text=HLO_SAMPLE, chips=256, model_flops_global=197e12 * 256)
    assert rep.compute_s == pytest.approx(1.0)
    assert rep.memory_s == pytest.approx(2.0)
    assert rep.dominant == "memory"
    assert rep.useful_flops_ratio == pytest.approx(1.0)


def test_extrapolate_affine():
    from repro.launch.dryrun import extrapolate_affine

    # cost(L) = 10 (outside) + 3 per layer -> c1 = 13, c2 = 16.
    assert extrapolate_affine(13.0, 16.0, 28) == pytest.approx(10 + 3 * 28)
    assert extrapolate_affine(5.0, 5.0, 100) == 5.0


def test_resolve_spec_drops_unknown_axes():
    from repro.core.utils import make_mesh

    mesh = make_mesh((1,), ("data",))
    spec = resolve_spec((("pod", "data"), None, "model"), mesh)
    assert spec == jax.sharding.PartitionSpec("data", None, None)


def test_adapt_for_batch1_decode_config_surgery():
    from repro.launch.dryrun import adapt_for_batch1_decode

    spec = registry.get_spec("gemma2-27b")
    cfg = spec.make_model()
    adapt_for_batch1_decode(cfg)
    attn = cfg.decoder.stack.layer.layers[0].self_attention
    # Batch axes gone from activations; cache seq (B, Hkv, T, D) on "data".
    assert attn.kv_cache_partition[0] is None
    assert attn.kv_cache_partition[2] == "data"
    assert attn.hidden_partition[0] is None
    # Weight partitions untouched.
    assert attn.qkv_weight_partition == ("data", "model")


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


def test_stack_depth_detection():
    from repro.launch.dryrun import stack_depth

    assert stack_depth(registry.get_spec("qwen2-1.5b").make_model()) == 28
    assert stack_depth(registry.get_spec("jamba-1.5-large-398b").make_model()) == 9
    assert stack_depth(registry.get_spec("gemma2-27b").make_model()) == 23


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
