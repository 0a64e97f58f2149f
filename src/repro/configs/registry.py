"""Architecture registry: ``--arch <id>`` resolution + param accounting."""

from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Tuple

import jax

from repro.configs.common import SHAPES, ArchSpec
from repro.layers.base import ParameterSpec

_ARCH_MODULES = {
    "qwen2-1.5b": "repro.configs.qwen2_1_5b",
    "phi-3-vision-4.2b": "repro.configs.phi3_vision_4_2b",
    "qwen1.5-4b": "repro.configs.qwen1_5_4b",
    "jamba-1.5-large-398b": "repro.configs.jamba_1_5_large_398b",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "arctic-480b": "repro.configs.arctic_480b",
    "gemma2-27b": "repro.configs.gemma2_27b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "hubert-xlarge": "repro.configs.hubert_xlarge",
    "internlm2-1.8b": "repro.configs.internlm2_1_8b",
    # Paper's own eval model (extra, not in the assigned pool):
    "llama2-7b": "repro.configs.llama2_7b",
    # Benchmark configurations beyond the assigned pool:
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
}
_EXTRA_ARCHS = ("llama2-7b", "moonlight-16b-a3b")

ASSIGNED_ARCHS: List[str] = [a for a in _ARCH_MODULES if a not in _EXTRA_ARCHS]
ALL_ARCHS: List[str] = list(_ARCH_MODULES)
SHAPE_NAMES: List[str] = list(SHAPES)


def get_spec(arch_id: str) -> ArchSpec:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"Unknown arch {arch_id!r}; known: {ALL_ARCHS}")
    mod = importlib.import_module(_ARCH_MODULES[arch_id])
    return mod.SPEC


def param_counts(model_cfg) -> Tuple[int, int]:
    """(total_params, active_params). Active discounts MoE expert weights by
    top_k/num_experts (the 6*N_active*D convention for MoE FLOPs)."""
    model = model_cfg.clone(name="tmp").instantiate()
    specs = model.create_parameter_specs_recursively()
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, ParameterSpec))[0]

    # Collect MoE (top_k, num_experts) by traversing the config.
    from repro.core.config import visit_config
    moe_ratio: Dict[str, float] = {}

    def visit(path, cfg):
        if type(cfg).__qualname__.startswith("MoELayer"):
            if "num_experts" in cfg.keys() and cfg.num_experts:
                moe_ratio["ratio"] = min(
                    moe_ratio.get("ratio", 1.0), cfg.top_k / cfg.num_experts)

    visit_config(model_cfg, visit)
    ratio = moe_ratio.get("ratio", 1.0)

    total = active = 0
    for path, spec in flat:
        n = 1
        for s in spec.shape:
            n *= int(s)
        total += n
        key = jax.tree_util.keystr(path)
        is_expert = ("moe" in key and ("'wi" in key or "'wo'" in key))
        active += int(n * ratio) if is_expert else n
    return total, active


def supported_pairs() -> List[Tuple[str, str]]:
    """All (arch, shape) pairs that run (vs documented skips)."""
    out = []
    for arch in ASSIGNED_ARCHS:
        spec = get_spec(arch)
        for shape in SHAPE_NAMES:
            if spec.supports(shape):
                out.append((arch, shape))
    return out


def skipped_pairs() -> List[Tuple[str, str, str]]:
    out = []
    for arch in ASSIGNED_ARCHS:
        spec = get_spec(arch)
        for shape, reason in spec.skip_shapes.items():
            out.append((arch, shape, reason))
    return out
