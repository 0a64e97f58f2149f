"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret=True mode on CPU (the kernel body executes with
real Python/jnp semantics), which validates the block decomposition, masking,
and online-softmax logic exactly as Mosaic would execute it on TPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.registry import KernelConfig
from repro.kernels.flash_attention import flash_attention, flash_attention_forward
from repro.kernels.rmsnorm import rmsnorm_forward


def _mk_qkv(key, B, S, T, Hq, Hkv, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    return q, k, v


SHAPE_SWEEP = [
    # B, S, Hq, Hkv, D, block_q, block_k
    (1, 128, 1, 1, 64, 64, 64),
    (2, 256, 4, 2, 32, 128, 128),
    (1, 384, 4, 1, 64, 128, 128),   # ragged: S not multiple of block
    (2, 100, 2, 2, 32, 64, 64),     # pad both dims
    (1, 256, 8, 2, 128, 128, 64),   # GQA 4:1, MXU-aligned D
]


@pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk", SHAPE_SWEEP)
def test_flash_matches_ref_causal(B, S, Hq, Hkv, D, bq, bk):
    q, k, v = _mk_qkv(jax.random.PRNGKey(0), B, S, S, Hq, Hkv, D)
    out = flash_attention_forward(q, k, v, causal=True, block_q=bq, block_k=bk,
                                  interpret=True)
    expect = ref.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_sliding_window(window):
    q, k, v = _mk_qkv(jax.random.PRNGKey(1), 1, 256, 256, 2, 2, 32)
    out = flash_attention_forward(q, k, v, causal=True, sliding_window=window,
                                  block_q=64, block_k=64, interpret=True)
    expect = ref.reference_attention(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_flash_softcap_and_noncausal():
    q, k, v = _mk_qkv(jax.random.PRNGKey(2), 2, 128, 128, 2, 1, 32)
    out = flash_attention_forward(q, k, v, causal=False, logit_softcap=30.0,
                                  block_q=64, block_k=64, interpret=True)
    expect = ref.reference_attention(q, k, v, causal=False, logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16_io():
    q, k, v = _mk_qkv(jax.random.PRNGKey(3), 1, 128, 128, 2, 2, 64, dtype=jnp.bfloat16)
    out = flash_attention_forward(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    expect = ref.reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=2e-2)


@given(
    st.integers(1, 2),                     # B
    st.sampled_from([64, 96, 128, 200]),   # S
    st.sampled_from([(2, 1), (2, 2), (4, 2)]),  # heads
    st.sampled_from([32, 64]),             # D
    st.booleans(),                         # causal
)
@settings(max_examples=12, deadline=None)
def test_flash_property_sweep(B, S, heads, D, causal):
    Hq, Hkv = heads
    q, k, v = _mk_qkv(jax.random.PRNGKey(S * 7 + D), B, S, S, Hq, Hkv, D)
    out = flash_attention_forward(q, k, v, causal=causal, block_q=64, block_k=64,
                                  interpret=True)
    expect = ref.reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=3e-5, rtol=3e-5)


# ------------------------ flash attention backward ---------------------------
#
# The recompute-based custom_vjp (dKV + dQ Pallas passes) must match
# jax.grad of the reference oracle — this is what makes the pallas backend
# legal as the *training* kernel, not just the serving path.


def _grad_parity(B, S, Hq, Hkv, D, *, causal=True, window=None, cap=None,
                 bq=64, bk=64, tol=3e-4, seed=0):
    q, k, v = _mk_qkv(jax.random.PRNGKey(seed), B, S, S, Hq, Hkv, D)
    do = jax.random.normal(jax.random.PRNGKey(seed + 1), q.shape)

    def f_flash(q, k, v):
        return jnp.sum(do * flash_attention(
            q, k, v, causal=causal, sliding_window=window, logit_softcap=cap,
            block_q=bq, block_k=bk, interpret=True))

    def f_ref(q, k, v):
        return jnp.sum(do * ref.reference_attention(
            q, k, v, causal=causal, sliding_window=window, logit_softcap=cap))

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("B,S,Hq,Hkv,D,bq,bk", SHAPE_SWEEP)
def test_flash_backward_matches_ref_grads(B, S, Hq, Hkv, D, bq, bk):
    _grad_parity(B, S, Hq, Hkv, D, bq=bq, bk=bk)


def test_flash_backward_gqa_sliding_window():
    _grad_parity(1, 256, 4, 1, 64, window=64)


def test_flash_backward_softcap_noncausal():
    _grad_parity(2, 128, 2, 1, 32, causal=False, cap=30.0)


def test_flash_backward_ragged_padding():
    # S not a block multiple: padded q rows/k cols must contribute nothing.
    _grad_parity(1, 100, 2, 2, 32, tol=5e-4)


def test_flash_value_and_grad_under_jit():
    """The flash kernel composes with jit + value_and_grad (the train step)."""
    q, k, v = _mk_qkv(jax.random.PRNGKey(5), 1, 128, 128, 2, 2, 32)

    @jax.jit
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True) ** 2)

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    ref_val = jnp.sum(ref.reference_attention(q, k, v, causal=True) ** 2)
    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-5)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_attention_layer_flash_grads_match_ref_impl():
    """End-to-end layer gradients: pallas backend == ref backend under grad."""
    from repro.core.module import functional
    from repro.layers import MultiheadAttention

    cfg = MultiheadAttention.default_config().set(
        name="a", input_dim=64, num_heads=4, num_kv_heads=2,
        kernel=KernelConfig().set(backend="pallas", interpret=True))
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 128, 64))

    def loss(state, layer):
        out, _ = functional(layer, state=state, inputs=(x,))
        return jnp.sum(out ** 2)

    g_flash = jax.grad(loss)(state, layer)
    g_ref = jax.grad(loss)(
        state, cfg.clone(kernel=KernelConfig().set(backend="ref")).instantiate())
    for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# ------------------------------ RMSNorm --------------------------------------


@pytest.mark.parametrize("shape,block_rows", [
    ((4, 7, 64), 8),
    ((2, 256, 128), 256),
    ((1, 100, 32), 64),   # row padding
])
def test_rmsnorm_kernel_matches_ref(shape, block_rows):
    x = jax.random.normal(jax.random.PRNGKey(4), shape)
    scale = jax.random.normal(jax.random.PRNGKey(5), (shape[-1],))
    out = rmsnorm_forward(x, scale, block_rows=block_rows, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reference_rmsnorm(x, scale)),
                               atol=1e-6, rtol=1e-5)


@given(st.sampled_from([16, 64, 128]), st.integers(1, 300))
@settings(max_examples=15, deadline=None)
def test_rmsnorm_property_sweep(D, rows):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, D))
    scale = jnp.ones((D,))
    out = rmsnorm_forward(x, scale, block_rows=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reference_rmsnorm(x, scale)),
                               atol=1e-6, rtol=1e-5)


# ------------------------- dispatch wrapper ----------------------------------


def test_ops_dispatch_decode_falls_back():
    """1-token decode (distinct cache positions) must route to the ref path."""
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 1, 2, 32))
    k = jax.random.normal(jax.random.PRNGKey(7), (1, 16, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(8), (1, 16, 2, 32))
    qp = jnp.array([10])
    kp = jnp.arange(16)
    out = ops.flash_attention(q, k, v, q_positions=qp, k_positions=kp,
                              causal=True,
                              kernel=KernelConfig().set(interpret=True))
    expect = ref.reference_attention(q, k, v, q_positions=qp, k_positions=kp,
                                     causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-5)


def test_attention_layer_flash_impl_matches_ref_impl():
    """End-to-end through the layer: pallas (interpret) == ref backend."""
    from repro.core.module import functional
    from repro.layers import MultiheadAttention

    cfg = MultiheadAttention.default_config().set(
        name="a", input_dim=64, num_heads=4, num_kv_heads=2,
        kernel=KernelConfig().set(backend="pallas", interpret=True))
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 128, 64))
    out_flash, _ = functional(layer, state=state, inputs=(x,))
    cfg2 = cfg.clone(kernel=KernelConfig().set(backend="ref"))
    layer2 = cfg2.instantiate()
    out_ref, _ = functional(layer2, state=state, inputs=(x,))
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


# ------------------------------ WKV6 kernel ----------------------------------


def _mk_wkv(key, B, T, H, K, V):
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, T, H, K))
    k = jax.random.normal(ks[1], (B, T, H, K))
    v = jax.random.normal(ks[2], (B, T, H, V))
    w = jax.random.uniform(ks[3], (B, T, H, K), minval=0.55, maxval=0.995)
    u = jax.random.normal(ks[4], (H, K)) * 0.5
    return r, k, v, w, u


@pytest.mark.parametrize("B,T,H,K,V,chunk", [
    (1, 32, 1, 8, 8, 8),
    (2, 64, 2, 16, 16, 16),
    (1, 128, 2, 32, 32, 32),
    (2, 45, 2, 16, 16, 16),  # ragged: padded with identity steps
])
def test_wkv6_kernel_matches_recurrent_oracle(B, T, H, K, V, chunk):
    from repro.kernels.wkv6 import wkv6_forward

    r, k, v, w, u = _mk_wkv(jax.random.PRNGKey(10), B, T, H, K, V)
    out, s = wkv6_forward(r, k, v, w, u, chunk_size=chunk, interpret=True)
    expect, s_ref = ref.reference_wkv6_recurrent(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_kernel_with_initial_state():
    from repro.kernels.wkv6 import wkv6_forward

    B, T, H, K, V = 1, 32, 2, 8, 8
    r, k, v, w, u = _mk_wkv(jax.random.PRNGKey(11), B, T, H, K, V)
    s0 = jax.random.normal(jax.random.PRNGKey(12), (B, H, K, V)).astype(jnp.float32)
    out, s = wkv6_forward(r, k, v, w, u, s0, chunk_size=8, interpret=True)
    expect, s_ref = ref.reference_wkv6_recurrent(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_ragged_falls_back_to_ref():
    """A ragged length stays on the kernel (padded with identity steps):
    outputs and the final state match the recurrence."""
    from repro.kernels import ops

    B, T, H, K, V = 1, 30, 1, 8, 8  # T not divisible by chunk
    r, k, v, w, u = _mk_wkv(jax.random.PRNGKey(13), B, T, H, K, V)
    cfg = KernelConfig().set(wkv_chunk_size=8, interpret=True)
    assert ops.registry.resolve_backend(
        "wkv6", ops.KernelFeatures(), cfg).backend == "pallas:interpret"
    out, s = ops.wkv6(r, k, v, w, u, kernel=cfg)
    expect, s_ref = ref.reference_wkv6_recurrent(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-4)
