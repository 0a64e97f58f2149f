"""Decode fast path: flash-decode kernel parity, scan-generate equivalence,
bucketed admission, and the no-logits-materialization guarantee.

Kernel tests run the Pallas body under interpret=True (CPU), which executes
the exact block decomposition and online-softmax updates Mosaic would run on
TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.module import functional
from repro.inference.engine import InferenceEngine, Request
from repro.kernels import ops, ref
from repro.kernels.registry import KernelConfig

# interpret=True -> the registry auto-selects pallas:interpret (the exact
# Mosaic block decomposition, executed on CPU).
INTERP = KernelConfig().set(interpret=True)
from repro.layers import CausalLM, Decoder, Repeat, TransformerLayer


def _mk_qkv(key, B, Sq, T, Hq, Hkv, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), dtype)
    return q, k, v


def _hm(cache):
    """(B, T, Hkv, D) oracle layout -> the head-major (B, Hkv, T, D) cache."""
    return jnp.swapaxes(cache, 1, 2)


def _check_parity(q, k, v, q_pos, k_pos, **kw):
    out = ops.decode_attention(
        q, _hm(k), _hm(v), q_positions=q_pos, k_positions=k_pos,
        kernel=INTERP, **kw)
    expect = ref.reference_attention(
        q, k, v, q_positions=q_pos, k_positions=k_pos, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


# ------------------------------- kernel parity -------------------------------


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (4, 1)])
def test_flash_decode_gqa_parity(Hq, Hkv):
    """GQA ratios 1/2/4: rows of one q block cover the whole KV group."""
    B, T, D = 2, 33, 16
    q, k, v = _mk_qkv(jax.random.PRNGKey(0), B, 1, T, Hq, Hkv, D)
    k_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_pos = jnp.full((B, 1), T)
    _check_parity(q, k, v, q_pos, k_pos)


def test_flash_decode_multi_step_causal():
    """S' > 1 decode steps mask causally among themselves."""
    B, Sq, T, D = 1, 3, 16, 8
    q, k, v = _mk_qkv(jax.random.PRNGKey(1), B, Sq, T, 4, 2, D)
    # Cache holds positions 0..12 plus the 3 new tokens at 13,14,15.
    k_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_pos = jnp.asarray([[13, 14, 15]])
    _check_parity(q, k, v, q_pos, k_pos)


@pytest.mark.parametrize("T", [256, 300])
def test_flash_decode_multi_block_cache(T):
    """A cache longer than one KV tile streams in lane-aligned blocks; a
    length that is not a whole number of blocks is never padded — the last
    block's tail is masked in the kernel."""
    from repro.kernels.flash_decode import flash_decode_forward

    B, D = 2, 16
    q, k, v = _mk_qkv(jax.random.PRNGKey(7), B, 1, T, 4, 2, D)
    k_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_pos = jnp.asarray([[T], [T // 2]])
    out = flash_decode_forward(q, _hm(k), _hm(v), q_pos, k_pos, block_k=128,
                               interpret=True)
    expect = ref.reference_attention(q, k, v, q_positions=q_pos,
                                     k_positions=k_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_ring_wraparound():
    """Ring layout: slot s holds position p with p % T == s — masking reads
    the pos tensor, so physical order is irrelevant."""
    B, T, D = 2, 8, 16
    q, k, v = _mk_qkv(jax.random.PRNGKey(2), B, 1, T, 4, 2, D)
    # 11 tokens written into an 8-slot ring: slots hold [8,9,10,3,4,5,6,7].
    ring = jnp.asarray([8, 9, 10, 3, 4, 5, 6, 7])
    k_pos = jnp.broadcast_to(ring, (B, T))
    q_pos = jnp.full((B, 1), 11)
    _check_parity(q, k, v, q_pos, k_pos, sliding_window=8)


def test_flash_decode_sliding_window():
    B, T, D = 1, 40, 16
    q, k, v = _mk_qkv(jax.random.PRNGKey(3), B, 1, T, 2, 2, D)
    k_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_pos = jnp.full((B, 1), T)
    _check_parity(q, k, v, q_pos, k_pos, sliding_window=7)


def test_flash_decode_softcap_and_scale():
    B, T, D = 1, 24, 16
    q, k, v = _mk_qkv(jax.random.PRNGKey(4), B, 1, T, 4, 2, D)
    k_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_pos = jnp.full((B, 1), T)
    _check_parity(q, k, v, q_pos, k_pos, logit_softcap=30.0, scale=0.2)


def test_flash_decode_partial_and_empty_slots():
    """Slots with pos = -1 (not yet written) are masked; a fully-masked row
    (empty continuous-batching slot) returns zeros — finite, never NaN."""
    B, T, D = 2, 12, 8
    q, k, v = _mk_qkv(jax.random.PRNGKey(5), B, 1, T, 4, 2, D)
    valid = jnp.asarray([0, 1, 2, 3] + [-1] * (T - 4))
    k_pos = jnp.stack([valid, jnp.full((T,), -1)])  # row 1: empty slot
    q_pos = jnp.asarray([[4], [0]])
    out = ops.decode_attention(q, _hm(k), _hm(v), q_positions=q_pos,
                               k_positions=k_pos, kernel=INTERP)
    expect = ref.reference_attention(q, k, v, q_positions=q_pos,
                                     k_positions=k_pos)
    # Row 0 has valid keys: exact parity with the reference oracle.
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expect[0]),
                               atol=2e-5, rtol=2e-5)
    # Row 1 is fully masked: the kernel defines the output as zeros (the
    # reference degenerates to a uniform average; both are unused downstream).
    assert np.isfinite(np.asarray(out[1])).all()
    np.testing.assert_allclose(np.asarray(out[1]), 0.0, atol=1e-6)


def test_flash_decode_bf16_inputs():
    B, T, D = 1, 32, 16
    q, k, v = _mk_qkv(jax.random.PRNGKey(6), B, 1, T, 4, 2, D, jnp.bfloat16)
    k_pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_pos = jnp.full((B, 1), T)
    out = ops.decode_attention(q, _hm(k), _hm(v), q_positions=q_pos,
                               k_positions=k_pos, kernel=INTERP)
    expect = ref.reference_attention(q, k, v, q_positions=q_pos,
                                     k_positions=k_pos)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=2e-2)


# ------------------------- flash_attention dispatch --------------------------


def test_flash_attention_equal_positions_uses_kernel():
    """Equal-by-value (but distinct) position arrays must NOT fall back to
    the O(S*T)-materializing reference path."""
    B, S, H, D = 1, 128, 2, 32
    q, k, v = _mk_qkv(jax.random.PRNGKey(7), B, S, S, H, H, D)
    # Two equal-valued but DISTINCT concrete arrays (the caller pattern the
    # old identity check broke on). Closed over — i.e. concrete — inside the
    # traced function; traced positions still fall back conservatively.
    qp, kp = jnp.arange(S), jnp.arange(S)
    assert qp is not kp
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: ops.flash_attention(
            q, k, v, q_positions=qp, k_positions=kp, kernel=INTERP))(q, k, v)
    assert "pallas_call" in str(jaxpr), \
        "equal-but-distinct positions fell back to the reference path"
    out = ops.flash_attention(q, k, v, q_positions=qp, k_positions=kp,
                              kernel=INTERP)
    expect = ref.reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


# --------------------------- engine: scan generate ---------------------------


def _tiny_lm(vocab=48, dim=32, L=2, window=None, decode_backend="ref"):
    layer = TransformerLayer.default_config().set(input_dim=dim)
    kernel = KernelConfig().set(
        op_overrides={"attention.decode": decode_backend},
        interpret=(decode_backend == "pallas"))
    layer.self_attention.set(num_heads=4, num_kv_heads=2, kernel=kernel,
                             kv_cache_dtype=jnp.float32, sliding_window=window)
    layer.feed_forward.set(hidden_dim=dim * 2)
    return CausalLM.default_config().set(
        name="lm",
        decoder=Decoder.default_config().set(
            vocab_size=vocab, dim=dim,
            stack=Repeat.default_config().set(layer=layer, num_layers=L,
                                              remat_policy=None)))


def _engine(model_cfg, max_len=32, slots=4):
    cfg = InferenceEngine.default_config().set(
        name="engine", model=model_cfg, max_len=max_len, slots=slots)
    engine = cfg.instantiate()
    params = engine.model.initialize_parameters_recursively(jax.random.PRNGKey(0))
    engine.load(params)
    return engine, params


def _stepwise_generate(engine, prompts, max_new_tokens, temperature, seed):
    """The pre-scan per-token host loop (one dispatch + sync per token) —
    the semantics oracle for the fused scan decode loop."""
    params = engine._params
    cache = engine.init_cache(prompts.shape[0])
    prefill = jax.jit(engine.prefill_fn())
    decode = jax.jit(engine.serve_step_fn())
    cache, logits = prefill(params, cache, jnp.asarray(prompts))
    key = jax.random.PRNGKey(seed)
    outs = []
    for _ in range(max_new_tokens):
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        outs.append(nxt)
        cache, logits = decode(params, cache, nxt[:, None])
    return np.asarray(jnp.stack(outs, axis=1))


def test_scan_generate_matches_stepwise_greedy():
    engine, _ = _engine(_tiny_lm())
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 48))
    tokens, _ = engine.generate(prompts, max_new_tokens=7)
    expect = _stepwise_generate(engine, prompts, 7, 0.0, 0)
    np.testing.assert_array_equal(tokens, expect)


def test_scan_generate_matches_stepwise_temperature():
    """Fixed-seed temperature sampling: the scan loop threads the PRNG key
    through its carry with the same split order as the host loop."""
    engine, _ = _engine(_tiny_lm())
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 48))
    tokens, _ = engine.generate(prompts, max_new_tokens=6, temperature=0.7,
                                seed=5)
    expect = _stepwise_generate(engine, prompts, 6, 0.7, 5)
    np.testing.assert_array_equal(tokens, expect)


def test_generate_flash_decode_matches_ref_impl():
    """The decode backend is semantics-free: pallas (interpret) == ref."""
    engine_ref, _ = _engine(_tiny_lm(decode_backend="ref"))
    engine_fd, _ = _engine(_tiny_lm(decode_backend="pallas"))
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 48))
    t_ref, _ = engine_ref.generate(prompts, max_new_tokens=6)
    t_fd, _ = engine_fd.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(t_ref, t_fd)


def test_generate_flash_decode_sliding_window_matches_ref():
    engine_ref, _ = _engine(_tiny_lm(window=8, decode_backend="ref"),
                            max_len=64)
    engine_fd, _ = _engine(_tiny_lm(window=8, decode_backend="pallas"),
                           max_len=64)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, 48))
    t_ref, _ = engine_ref.generate(prompts, max_new_tokens=6)
    t_fd, _ = engine_fd.generate(prompts, max_new_tokens=6)
    np.testing.assert_array_equal(t_ref, t_fd)


def _jaxpr_shapes(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", None)
            if inner is not None:
                _jaxpr_shapes(inner, acc)
            if isinstance(param, (list, tuple)):
                for p in param:
                    inner = getattr(p, "jaxpr", None)
                    if inner is not None:
                        _jaxpr_shapes(inner, acc)
    return acc


def test_flash_decode_never_materializes_decode_logits():
    """The acceptance guarantee: with the pallas decode backend no
    intermediate of shape (B, Hkv, G, S', T) exists anywhere in the decode
    step program; with 'ref' it does."""
    B, T = 2, 32
    shapes = {}
    for impl in ("ref", "pallas"):
        engine, params = _engine(_tiny_lm(decode_backend=impl), max_len=T)
        cache = engine.init_cache(B)
        step = engine.serve_step_fn()
        ids = jnp.zeros((B, 1), jnp.int32)
        jaxpr = jax.make_jaxpr(step)(params, cache, ids)
        shapes[impl] = _jaxpr_shapes(jaxpr.jaxpr, set())
    logits_shape = (B, 2, 2, 1, T)  # (B, Hkv, G, S'=1, T)
    assert logits_shape in shapes["ref"], \
        "expected the ref decode path to materialize attention logits"
    assert logits_shape not in shapes["pallas"], \
        "flash_decode materialized the (B,Hkv,G,S',T) logits tensor"


# ------------------------- engine: bucketed admission ------------------------


def test_bucket_len_policy():
    engine, _ = _engine(_tiny_lm(), max_len=48)
    assert engine._bucket_len(1) == 8
    assert engine._bucket_len(8) == 8
    assert engine._bucket_len(9) == 16
    assert engine._bucket_len(17) == 32
    # Prompts longer than max_len still bucket (ring cache keeps the last
    # T valid tokens, recurrent mixers consume the whole prompt).
    assert engine._bucket_len(49) == 64


def test_serve_prompt_longer_than_max_len_matches_generate():
    """Over-long prompts are served through the ring cache, exactly like
    batched generation (a per-request error must not abort the batch)."""
    engine, _ = _engine(_tiny_lm(), max_len=16, slots=2)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 48, size=(n,)) for n in (24, 6)]
    reqs = [Request(request_id=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    results = engine.serve(reqs)
    for i, res in enumerate(results):
        expect, _ = engine.generate(prompts[i][None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(res.tokens), expect[0])


def test_serve_mixed_prompt_lengths_matches_generate():
    """Bucketed admission is exact: prompts of different lengths (padded to
    different buckets) produce the same greedy tokens as unpadded
    single-request generation."""
    engine, _ = _engine(_tiny_lm(), max_len=32, slots=2)
    rng = np.random.default_rng(0)
    lens = [5, 9, 16, 3, 12]
    prompts = [rng.integers(0, 48, size=(n,)) for n in lens]
    reqs = [Request(request_id=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    results = engine.serve(reqs)
    for i, res in enumerate(results):
        expect, _ = engine.generate(prompts[i][None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(res.tokens), expect[0])


def test_serve_mixed_lengths_rwkv():
    """Recurrent mixers: bucket padding must not pollute the wkv/shift state
    (identity transitions on padded steps)."""
    from repro.layers.rwkv import RWKV6Block

    block = RWKV6Block.default_config().set(input_dim=32)
    block.time_mix.set(head_dim=16, decay_lora_dim=8)
    block.time_mix.kernel.set(wkv_chunk_size=4)
    block.channel_mix.set(hidden_dim=64)
    model = CausalLM.default_config().set(
        name="lm",
        decoder=Decoder.default_config().set(
            vocab_size=48, dim=32,
            stack=Repeat.default_config().set(layer=block, num_layers=2,
                                              remat_policy=None)))
    engine, _ = _engine(model, max_len=32, slots=2)
    rng = np.random.default_rng(1)
    lens = [6, 11, 3]
    prompts = [rng.integers(0, 48, size=(n,)) for n in lens]
    reqs = [Request(request_id=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    results = engine.serve(reqs)
    for i, res in enumerate(results):
        expect, _ = engine.generate(prompts[i][None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(res.tokens), expect[0])


def test_serve_mixed_lengths_mamba():
    from repro.layers.ssm import MambaMixer

    layer = TransformerLayer.default_config().set(input_dim=32)
    # scan_chunk_size=8: the 16-bucket admissions exercise the CHUNKED
    # masked scan (long buckets must not materialize (B,S,di,N) states).
    layer.self_attention = MambaMixer.default_config().set(
        state_dim=8, conv_width=3, scan_chunk_size=8)
    layer.feed_forward.set(hidden_dim=64)
    model = CausalLM.default_config().set(
        name="lm",
        decoder=Decoder.default_config().set(
            vocab_size=48, dim=32,
            stack=Repeat.default_config().set(layer=layer, num_layers=2,
                                              remat_policy=None)))
    engine, _ = _engine(model, max_len=32, slots=2)
    rng = np.random.default_rng(2)
    lens = [7, 12, 4]
    prompts = [rng.integers(0, 48, size=(n,)) for n in lens]
    reqs = [Request(request_id=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    results = engine.serve(reqs)
    for i, res in enumerate(results):
        expect, _ = engine.generate(prompts[i][None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(res.tokens), expect[0])


def test_decode_attention_requires_positions():
    q, k, v = _mk_qkv(jax.random.PRNGKey(8), 1, 1, 8, 2, 2, 8)
    with pytest.raises(ValueError, match="explicit q_positions"):
        ops.decode_attention(q, k, v, q_positions=None,
                             k_positions=jnp.arange(8), kernel=INTERP)


def test_flash_decode_allows_single_device_mesh():
    """The sharded-cache guard only trips on real >1-way sharding: a
    1-device mesh (names resolve but sizes are 1) must pass."""
    from repro.core.utils import make_mesh, set_mesh

    engine, _ = _engine(_tiny_lm(decode_backend="pallas"))
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0, 48))
    with set_mesh(make_mesh((1,), ("data",))):
        tokens, _ = engine.generate(prompts, max_new_tokens=3)
    assert tokens.shape == (2, 3)


def test_admission_is_compile_bounded():
    """Admissions at different slots / true lengths within one bucket reuse
    one compiled program (traced scalars, not shape specializations)."""
    engine, _ = _engine(_tiny_lm(), max_len=32, slots=2)
    rng = np.random.default_rng(3)
    # Lengths 5..8 share the 8-bucket: first admit compiles, rest must not.
    reqs = [Request(request_id=i, prompt=rng.integers(0, 48, size=(5 + i,)),
                    max_new_tokens=2) for i in range(4)]
    engine.serve([reqs[0]])
    admit = engine._jit_fns["admit"]
    compiles_after_first = admit._cache_size()
    engine.serve(reqs[1:])
    assert admit._cache_size() == compiles_after_first, \
        "same-bucket admissions recompiled"
