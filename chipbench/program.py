"""How the benchmark builds the system under test: through the program's
own entry points, at the sizes of the configuration file, checked leaf by
leaf against the plain reference's layout."""

from __future__ import annotations

import os
from typing import Any, Dict

from chipbench import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def reference(cfg):
    """The configuration's plain reference module (``configs/<name>.py``)."""
    return harness.load_module(os.path.join(HERE, "configs",
                                            f"{cfg['reference']}.py"))


def apply_sizes(model_cfg, cfg: Dict[str, Any]):
    """Sets the configuration file's sizes on the program's model config
    (a ``CausalLM`` over a ``Repeat`` of ``TransformerLayer``)."""
    dec = model_cfg.decoder
    dec.set(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"])
    dec.final_norm.set(eps=cfg["rms_norm_eps"])
    dec.stack.set(num_layers=cfg["num_hidden_layers"])
    layer = dec.stack.layer
    layer.set(input_dim=cfg["hidden_size"])
    layer.norm.set(eps=cfg["rms_norm_eps"])
    hq = cfg["num_attention_heads"]
    layer.self_attention.set(
        num_heads=hq, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // hq)
    layer.self_attention.rope.set(theta=cfg["rope_theta"])
    layer.feed_forward.set(hidden_dim=cfg["intermediate_size"])
    return model_cfg


def check_layout(specs, cfg, ref):
    """Every leaf of the program's parameter tree has the shape the plain
    reference gives it, and there is no other leaf."""
    import jax

    from repro.layers.base import ParameterSpec

    want = ref.leaf_shapes(cfg)
    got = {jax.tree_util.keystr(p): tuple(s.shape) for p, s in
           jax.tree_util.tree_flatten_with_path(
               specs, is_leaf=lambda s: isinstance(s, ParameterSpec))[0]}
    mapped = {jax.tree_util.keystr(tuple(jax.tree_util.DictKey(k)
                                         for k in path)): want[name]
              for name, path in ref.PROGRAM_PATHS.items()}
    if got != mapped:
        raise SystemExit(f"chipbench: the program's parameters {got} are not "
                         f"the configuration's {mapped}")
