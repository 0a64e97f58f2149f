"""Int8 DotGeneral-swap quantization (paper §4.2 + App. A) and fp8 KV cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import config_for_function, replace_config
from repro.core.module import functional
from repro.layers import CausalLM, Decoder, Linear, Repeat, TransformerLayer
from repro.quantization.linear import (Int8ConfigModifier, QuantizedLinear,
                                       quantize_int8)


def test_quantize_int8_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    q, s = quantize_int8(x, axis=-1)
    deq = q.astype(jnp.float32) * s
    err = jnp.max(jnp.abs(deq - x))
    assert float(err) <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6


def test_quantized_linear_close_to_fp():
    cfg = Linear.default_config().set(name="l", input_dim=64, output_dim=32)
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    fp, _ = functional(layer, state=state, inputs=(x,))

    qcfg = QuantizedLinear.default_config().set(
        name="q", input_dim=64, output_dim=32)
    qlayer = qcfg.instantiate()
    q_out, _ = functional(qlayer, state=state, inputs=(x,))  # same checkpoint!
    rel = np.linalg.norm(np.asarray(q_out - fp)) / np.linalg.norm(np.asarray(fp))
    assert rel < 0.02, f"int8 relative error {rel}"


def test_quantized_linear_ste_gradients_flow():
    qcfg = QuantizedLinear.default_config().set(
        name="q", input_dim=16, output_dim=8)
    layer = qcfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16))

    def loss(s):
        out, _ = functional(layer, state=s, inputs=(x,), is_training=True,
                            prng_key=jax.random.PRNGKey(2))
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(state)
    assert float(jnp.linalg.norm(g["weight"])) > 0, "STE must pass gradients"


def _tiny_trainer_cfg():
    from repro.trainer import optimizers as opt_lib
    from repro.trainer.trainer import SpmdTrainer

    layer = TransformerLayer.default_config().set(input_dim=32)
    layer.self_attention.set(num_heads=4)
    layer.feed_forward.set(hidden_dim=64)
    model = CausalLM.default_config().set(
        decoder=Decoder.default_config().set(
            vocab_size=64, dim=32,
            stack=Repeat.default_config().set(layer=layer, num_layers=2,
                                              remat_policy=None)))
    cfg = SpmdTrainer.default_config().set(name="t", model=model, max_steps=8,
                                           log_every_n=4)
    cfg.input.set(task="lm", vocab_size=64, seq_len=16, global_batch_size=4)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(peak_lr=1e-3)
    return cfg


def test_int8_modifier_swaps_all_linears_and_trains():
    """The paper's quantization story end-to-end: one modifier swaps every
    Linear in the experiment; training still converges finitely."""
    cfg = _tiny_trainer_cfg()
    cfg = Int8ConfigModifier.default_config().instantiate().apply(cfg)
    # every Linear is now QuantizedLinear (q/k/v/o + lm head path if untied)
    from repro.core.config import visit_config

    kinds = []
    visit_config(cfg, lambda p, c: kinds.append(type(c).__qualname__))
    assert not any(k == "Linear.Config" for k in kinds)
    assert any("QuantizedLinear" in k for k in kinds)
    result = cfg.instantiate().run()
    assert np.isfinite(result["final"]["loss"])


def test_fp8_kv_cache_decode_close_to_bf16():
    """Hillclimb variant semantics: fp8(e4m3) cache decode stays close to the
    fp32-cache decode (argmax tokens may differ slightly; logits are close)."""
    from repro.layers import MultiheadAttention

    cfg = MultiheadAttention.default_config().set(
        name="a", input_dim=64, num_heads=4, num_kv_heads=2,
        kv_cache_dtype=jnp.float32)
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 64)) * 0.5

    def decode(c):
        l = c.instantiate()
        cache, _ = functional(l, state=state, inputs=(2, 16), method="init_states")
        (cache, y0), _ = functional(l, state=state,
                                    inputs={"state": cache, "x": x[:, :8]},
                                    method="prefill")
        (cache, y1), _ = functional(l, state=state,
                                    inputs={"state": cache, "x_step": x[:, 8:]},
                                    method="extend_step")
        return jnp.concatenate([y0, y1], axis=1)

    ref = decode(cfg)
    f8 = decode(cfg.clone(kv_cache_dtype=jnp.float8_e4m3fn))
    rel = np.linalg.norm(np.asarray(f8 - ref)) / np.linalg.norm(np.asarray(ref))
    assert rel < 0.06, f"fp8 cache relative error {rel}"
