"""Layer library tests: shapes, correctness, decode equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.module import functional
from repro.kernels import ref as kref
from repro.kernels.registry import KernelConfig
from repro.layers import (
    CausalLM,
    Decoder,
    Embedding,
    FeedForward,
    Linear,
    MultiheadAttention,
    RMSNorm,
    Repeat,
    RotaryEmbedding,
    StackedTransformer,
    TransformerLayer,
    scaled_hidden_dim,
)
from repro.layers.rope import LinearScaledRotaryEmbedding


def run(layer_cfg, inputs, *, state=None, method="forward", training=False, seed=0):
    layer = layer_cfg.instantiate()
    if state is None:
        state = layer.initialize_parameters_recursively(jax.random.PRNGKey(seed))
    out, col = functional(
        layer, state=state, inputs=inputs, is_training=training,
        prng_key=jax.random.PRNGKey(seed + 1), method=method)
    return layer, state, out, col


def test_linear_shapes_and_bias():
    cfg = Linear.default_config().set(name="l", input_dim=8, output_dim=16)
    _, state, out, _ = run(cfg, (jnp.ones((2, 3, 8)),))
    assert out.shape == (2, 3, 16)
    assert state["bias"].shape == (16,)


def test_embedding_attend_tied():
    cfg = Embedding.default_config().set(name="e", num_embeddings=11, dim=6)
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    ids = jnp.array([[1, 2, 3]])
    emb, _ = functional(layer, state=state, inputs=(ids,))
    logits, _ = functional(layer, state=state, inputs=(emb,), method="attend")
    assert logits.shape == (1, 3, 11)
    assert jnp.argmax(logits[0, 0]) == 1  # embedding should be closest to itself


def test_rmsnorm_matches_ref():
    cfg = RMSNorm.default_config().set(name="n", input_dim=32)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 5, 32))
    _, state, out, _ = run(cfg, (x,))
    np.testing.assert_allclose(
        out, kref.reference_rmsnorm(x, state["scale"]), rtol=1e-6)


def test_rope_rotation_preserves_norm_and_relativity():
    cfg = RotaryEmbedding.default_config().set(name="r", dim=16)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 2, 16))
    _, _, out, _ = run(cfg, (x, jnp.arange(6)), method="apply")
    np.testing.assert_allclose(
        jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # Relative property: <R(p)q, R(p+k)v> depends only on k.
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, 16))
    layer = cfg.instantiate()
    def rot(vec, pos):
        out, _ = functional(layer, state={}, inputs=(vec, jnp.array([pos])), method="apply")
        return out[0, 0, 0]
    d1 = jnp.dot(rot(q, 3), rot(q, 5))
    d2 = jnp.dot(rot(q, 10), rot(q, 12))
    np.testing.assert_allclose(d1, d2, rtol=1e-4)


def test_ffn_swiglu_and_scaled_hidden_dim():
    cfg = FeedForward.default_config().set(
        name="f", input_dim=12, hidden_dim=scaled_hidden_dim(8 / 3, round_to=8),
        activation=("linear", "nn.silu"))
    layer, state, out, _ = run(cfg, (jnp.ones((2, 3, 12)),))
    assert out.shape == (2, 3, 12)
    assert layer.config.hidden_dim == 32  # ceil(32/8)*8
    assert "up_proj0" in state and "up_proj1" in state


ATTN_VARIANTS = [
    dict(num_heads=4, num_kv_heads=4),
    dict(num_heads=4, num_kv_heads=2),  # GQA
    dict(num_heads=4, num_kv_heads=2, sliding_window=8),
    dict(num_heads=4, num_kv_heads=1, logit_softcap=20.0),
]


@pytest.mark.parametrize("variant", ATTN_VARIANTS)
def test_attention_blockwise_equals_ref(variant):
    cfg = MultiheadAttention.default_config().set(
        name="a", input_dim=32, qkv_bias=True,
        kernel=KernelConfig().set(backend="ref"), **variant)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    layer, state, out_ref, _ = run(cfg, (x,))
    cfg2 = cfg.clone(kernel=KernelConfig().set(
        backend="blockwise", blockwise_chunk_size=4))
    _, _, out_blk, _ = run(cfg2, (x,), state=state)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_blk), atol=1e-5)


@pytest.mark.parametrize("variant", ATTN_VARIANTS)
def test_attention_decode_matches_forward(variant):
    """prefill + extend_step token-by-token == full forward (unified
    train/inference, paper §6)."""
    S, D = 12, 32
    cfg = MultiheadAttention.default_config().set(
        name="a", input_dim=D, kv_cache_dtype=jnp.float32, **variant)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, S, D))
    layer, state, full, _ = run(cfg, (x,))

    cache, _ = functional(layer, state=state, inputs=(2, S), method="init_states")
    prefix = 5
    cache, y_pre, = None, None
    cache0, _ = functional(layer, state=state, inputs=(2, S), method="init_states")
    (cache, y_pre), _ = functional(
        layer, state=state, inputs={"state": cache0, "x": x[:, :prefix]}, method="prefill")
    np.testing.assert_allclose(np.asarray(y_pre), np.asarray(full[:, :prefix]), atol=2e-3)
    ys = [y_pre]
    for t in range(prefix, S):
        (cache, y), _ = functional(
            layer, state=state,
            inputs={"state": cache, "x_step": x[:, t:t + 1]}, method="extend_step")
        ys.append(y)
    decoded = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(decoded), np.asarray(full), atol=2e-3)


def test_sliding_window_cache_is_bounded():
    cfg = MultiheadAttention.default_config().set(
        name="a", input_dim=16, num_heads=2, sliding_window=4)
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    cache, _ = functional(layer, state=state, inputs=(1, 64), method="init_states")
    # Head-major (B, Hkv, T, D): the slot axis is 2.
    assert cache["k"].shape[2] == 4, "SWA cache must be window-sized (long_500k enabler)"


def _tiny_layer_cfg(dim=32, moe=False):
    cfg = TransformerLayer.default_config().set(name="t", input_dim=dim)
    cfg.self_attention.set(num_heads=4, num_kv_heads=2)
    cfg.feed_forward.set(hidden_dim=dim * 2, activation=("linear", "nn.silu"))
    return cfg


def test_transformer_layer_forward_and_decode():
    cfg = _tiny_layer_cfg()
    cfg.self_attention.kv_cache_dtype = jnp.float32
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 32))
    layer, state, full, _ = run(cfg, (x,))
    assert full.shape == x.shape
    cache, _ = functional(layer, state=state, inputs=(2, 8), method="init_states")
    (cache, y0), _ = functional(layer, state=state,
                                inputs={"state": cache, "x": x[:, :4]}, method="prefill")
    ys = [y0]
    for t in range(4, 8):
        (cache, y), _ = functional(layer, state=state,
                                   inputs={"state": cache, "x_step": x[:, t:t + 1]},
                                   method="extend_step")
        ys.append(y)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, 1)), np.asarray(full), atol=2e-3)


def test_repeat_matches_stacked_loop():
    """scan-over-layers == python loop with identical per-layer params."""
    layer_cfg = _tiny_layer_cfg()
    L = 3
    rep_cfg = Repeat.default_config().set(
        name="rep", layer=layer_cfg, num_layers=L, remat_policy=None)
    rep = rep_cfg.instantiate()
    rep_state = rep.initialize_parameters_recursively(jax.random.PRNGKey(1))

    stk_cfg = StackedTransformer.default_config().set(
        name="stk", layers=[layer_cfg.clone() for _ in range(L)])
    stk = stk_cfg.instantiate()
    stk_state = {
        f"layer{i}": jax.tree.map(lambda a: a[i], rep_state["layer"]) for i in range(L)
    }
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 8, 32))
    out_rep, _ = functional(rep, state=rep_state, inputs=(x,))
    out_stk, _ = functional(stk, state=stk_state, inputs=(x,))
    np.testing.assert_allclose(np.asarray(out_rep), np.asarray(out_stk), atol=1e-5)


def test_repeat_remat_same_loss_and_grads():
    layer_cfg = _tiny_layer_cfg()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, 32))

    def loss_fn(state, cfg):
        rep = cfg.instantiate()
        out, _ = functional(rep, state=state, inputs=(x,), is_training=True,
                            prng_key=jax.random.PRNGKey(0))
        return jnp.sum(out ** 2)

    cfg_a = Repeat.default_config().set(name="r", layer=layer_cfg, num_layers=2,
                                        remat_policy=None)
    cfg_b = cfg_a.clone(remat_policy="full")
    state = cfg_a.instantiate().initialize_parameters_recursively(jax.random.PRNGKey(1))
    la, ga = jax.value_and_grad(loss_fn)(state, cfg_a)
    lb, gb = jax.value_and_grad(loss_fn)(state, cfg_b)
    np.testing.assert_allclose(la, lb, rtol=1e-6)
    for (pa, pb) in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        # Remat recomputes the forward, which XLA may fuse/reassociate
        # differently; grads O(10-100) match to ~1e-4 absolute.
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb), atol=1e-4,
                                   rtol=1e-4)


def _tiny_lm_cfg(vocab=64, dim=32, L=2):
    layer_cfg = _tiny_layer_cfg(dim)
    layer_cfg.self_attention.kv_cache_dtype = jnp.float32
    dec = Decoder.default_config().set(
        name="d", vocab_size=vocab, dim=dim,
        stack=Repeat.default_config().set(layer=layer_cfg, num_layers=L,
                                          remat_policy=None))
    return CausalLM.default_config().set(name="lm", decoder=dec)


def test_causal_lm_loss_and_decode_equivalence():
    cfg = _tiny_lm_cfg()
    model = cfg.instantiate()
    state = model.initialize_parameters_recursively(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, 64)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    (loss, aux), col = functional(model, state=state, inputs=(batch,), is_training=True,
                                  prng_key=jax.random.PRNGKey(2))
    assert jnp.isfinite(loss)
    assert aux["logits"].shape == (2, 10, 64)
    # decode path == forward path logits
    logits_fwd = aux["logits"]
    cache, _ = functional(model, state=state, inputs=(2, 10), method="init_states")
    (cache, lg), _ = functional(model, state=state,
                                inputs={"state": cache, "input_ids": ids[:, :6]},
                                method="prefill")
    outs = [lg]
    for t in range(6, 10):
        (cache, lg), _ = functional(model, state=state,
                                    inputs={"state": cache, "ids_step": ids[:, t:t + 1]},
                                    method="extend_step")
        outs.append(lg)
    decoded = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(decoded), np.asarray(logits_fwd), atol=3e-3)


def test_rope_variant_swap_is_pure_config():
    """The paper's O(1) claim at layer level: swapping the RoPE child changes
    behaviour without touching attention code."""
    from repro.core.config import replace_config

    cfg = _tiny_lm_cfg()
    n = replace_config(
        cfg, target=RotaryEmbedding,
        new_cfg=LinearScaledRotaryEmbedding.default_config().set(scaling_factor=4.0),
        propagate=("dim", "theta"))
    assert n == 1  # one template inside the repeated layer
    model = cfg.instantiate()
    assert type(model.decoder.stack.layer.self_attention.rope).__name__ == \
        "LinearScaledRotaryEmbedding"


def test_chunked_loss_matches_full():
    """Token-chunked CE (memory lever for 256k vocab) == single-shot CE."""
    cfg = _tiny_lm_cfg()
    model = cfg.instantiate()
    state = model.initialize_parameters_recursively(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, 1)}
    (loss_full, _), _ = functional(model, state=state, inputs=(batch,))
    cfg2 = cfg.clone(loss_chunk_size=4)
    model2 = cfg2.instantiate()
    (loss_chunk, aux), _ = functional(model2, state=state, inputs=(batch,))
    np.testing.assert_allclose(np.asarray(loss_chunk), np.asarray(loss_full),
                               rtol=1e-6)
    assert aux["logits"] is None

    # Gradients agree too (remat inside the chunk scan).
    def lf(s, c):
        m = c.instantiate()
        (l, _), _ = functional(m, state=s, inputs=(batch,))
        return l

    g1 = jax.grad(lf)(state, cfg)
    g2 = jax.grad(lf)(state, cfg2)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
