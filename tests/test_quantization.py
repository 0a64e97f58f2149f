"""Low-precision end-to-end: the repro.quantization subsystem.

Covers the numerics contract (amax-in-fp32, already-quantized no-op,
round-trip error bounds per format), quantized paged-KV decode parity on
both the ref and (interpreted) Pallas backends, prefix-cache exactness on
shared quantized pages, scale-pool atomicity through every page-moving
manager op, fp8 delayed-scaling train parity vs bf16, the
QuantizationModifier config path, and the grep contract that keeps dtype
branching inside the subsystem.
"""

import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.quantization import kv as kvq
from repro.quantization.numerics import dequantize, quantize_int8
from repro.serving import SamplingParams, ServingGateway
from test_serving import _engine, _tiny_lm
from test_trainer import _tiny_trainer_cfg


# ------------------------------- numerics ------------------------------------


def test_quantize_int8_already_quantized_is_noop():
    x = jnp.arange(-4, 4, dtype=jnp.int8).reshape(2, 4)
    q, scale = quantize_int8(x, axis=-1)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), np.asarray(x))
    # Unit scales, shaped with the reduced axis kept as 1 (broadcastable).
    assert scale.shape == (2, 1)
    np.testing.assert_array_equal(np.asarray(scale), 1.0)


def test_quantize_int8_amax_in_fp32_for_bf16_inputs():
    # A bf16 tensor whose true amax is not representable in bf16 after
    # in-dtype reduction tricks: the scale must come from an fp32 amax.
    x = (jnp.array([100.0, -100.5, 3.0], jnp.float32)).astype(jnp.bfloat16)
    q, scale = quantize_int8(x, axis=-1)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    # amax computed in fp32 from the upcast values.
    expect = float(jnp.max(jnp.abs(x.astype(jnp.float32)))) / 127.0
    assert scale.shape == (1,)
    np.testing.assert_allclose(np.asarray(scale), expect, rtol=1e-6)
    deq = dequantize(q, scale)
    # Uniform int8: absolute error is bounded by half a step (amax / 254),
    # plus the bf16 representation error of the inputs themselves.
    step = float(scale[0])
    np.testing.assert_allclose(np.asarray(deq),
                               np.asarray(x, dtype=np.float32),
                               atol=step / 2 + 0.5)


@pytest.mark.parametrize("fmt,rel_bound", [
    (kvq.INT8_KV, 0.01),        # 8 uniform bits: ~1/254 max rel error
    (kvq.FP8_E4M3_KV, 0.07),    # e4m3: 3 mantissa bits, ~2^-4 grid
])
@pytest.mark.parametrize("magnitude", [1e-3, 1.0, 300.0])
def test_kv_write_roundtrip_error_bounds(fmt, rel_bound, magnitude):
    """Per-slot scaled storage keeps relative round-trip error inside the
    format's grid across 5+ decades of input magnitude."""
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(key, (4, 8, 2, 16), jnp.float32) * magnitude
    v = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 2, 16)) * magnitude
    kq, vq, scales = kvq.quantize_kv_write(k, v, fmt)
    assert kq.dtype == fmt.storage_dtype and scales.shape == (4, 8, 2)
    kd, vd = kvq.dequantize_kv(kq, vq, scales.reshape(4 * 8, 2).reshape(4, 8, 2))
    # Error is bounded relative to the per-slot amax (the quantization
    # reference), not per-element values.
    for orig, deq in ((k, kd), (v, vd)):
        amax = jnp.max(jnp.abs(orig), axis=(-2, -1), keepdims=True)
        err = jnp.max(jnp.abs(deq - orig) / amax)
        assert float(err) < rel_bound, (fmt.name, magnitude, float(err))


def test_pool_format_rules():
    assert kvq.pool_format("int8", layout="paged") is kvq.INT8_KV
    assert kvq.pool_format("fp8_e4m3", layout="paged") is kvq.FP8_E4M3_KV
    # fp8 on a dense ring keeps the plain-astype path (no scale rows there).
    assert kvq.pool_format(jnp.float8_e4m3fn, layout="dense") is None
    assert kvq.pool_format(jnp.float32, layout="paged") is None
    with pytest.raises(ValueError, match="paged"):
        kvq.pool_format("int8", layout="dense")


# --------------------- quantized paged decode parity -------------------------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_int8_paged_decode_token_parity(backend):
    """int8 KV storage (~1% error) must not flip greedy decode tokens vs
    the fp32 paged engine, on both the XLA-gather and Pallas in-kernel
    dequant paths."""
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 47, size=(2, 12))
    ref = _engine(_tiny_lm("paged", num_pages=25), max_len=32, slots=4)
    toks_ref, _ = ref.generate(prompts, max_new_tokens=10)

    from repro.quantization.modifier import set_kv_cache_dtype

    cfg = _tiny_lm("paged", num_pages=25, decode_backend=backend)
    set_kv_cache_dtype(cfg, "int8", paged_only=True)
    eng = _engine(cfg, max_len=32, slots=4)
    # Storage really is 8-bit (the density claim, not just a dtype tag).
    cache = eng.init_cache()
    k_pools = [l for l in jax.tree_util.tree_leaves(cache)
               if l.dtype == jnp.int8]
    assert k_pools, "no int8 pool leaves allocated"
    toks, _ = eng.generate(prompts, max_new_tokens=10)
    np.testing.assert_array_equal(toks, toks_ref)


@pytest.mark.parametrize("fmt_name,tol", [("int8", 0.02), ("fp8_e4m3", 0.1)])
def test_quantized_paged_kernel_output_close_to_fp32(fmt_name, tol):
    """Kernel-level parity: pallas(interpret) and ref paged decode over a
    quantized pool stay within the format's grid of the fp32 answer."""
    from repro.kernels.flash_decode import paged_flash_decode_forward

    fmt = kvq.format_by_name(fmt_name)
    P, page, Hkv, D, B, N, Hq = 6, 8, 2, 32, 2, 2, 4
    k = jax.random.normal(jax.random.PRNGKey(0), (P, page, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(1), (P, page, Hkv, D))
    pos = jnp.tile(jnp.arange(page)[None], (P, 1))
    tbl = jnp.array([[0, 2], [3, -1]], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, Hq, D))
    qpos = jnp.full((B, 1), 100, jnp.int32)

    # Quantize token-major rows (per-slot scales span heads x head_dim),
    # then store the pools head-major, (P, Hkv, page, D), as the layer does.
    kq, vq, scales = kvq.quantize_kv_write(k, v, fmt)
    k, v, kq, vq = (jnp.swapaxes(x, 1, 2) for x in (k, v, kq, vq))
    o_fp32 = paged_flash_decode_forward(q, k, v, pos, tbl, qpos,
                                        interpret=True)
    o_pal = paged_flash_decode_forward(q, kq, vq, pos, tbl, qpos,
                                       scale_pool=scales, interpret=True)
    assert float(jnp.max(jnp.abs(o_pal - o_fp32))) < tol
    o_ref = ops.decode_attention(q, kq, vq, q_positions=qpos, k_positions=pos,
                                 page_tables=tbl, scale_pool=scales)
    assert float(jnp.max(jnp.abs(o_ref - o_fp32))) < tol


def test_int8_requires_paged_layout():
    """Dense rings have no scale rows: the registry rejects int8 there and
    the layer refuses to build the config at all."""
    from repro.kernels.registry import (DEFAULT_CONFIG, KernelDispatchError,
                                        KernelFeatures)
    from repro.kernels import registry as kreg

    feats = KernelFeatures(platform="cpu", dtype="float32", paged=False,
                           kv_dtype="int8")
    cfg_pallas = DEFAULT_CONFIG.clone(
        op_overrides={"attention.decode": "pallas"}, interpret=True)
    with pytest.raises(KernelDispatchError, match="paged"):
        kreg.resolve_backend("attention.decode", feats, cfg_pallas)

    cfg = _tiny_lm()  # dense ring cache
    from repro.quantization.modifier import set_kv_cache_dtype
    with pytest.raises(ValueError, match="paged"):
        set_kv_cache_dtype(cfg, "int8")
        _engine(cfg)


# ------------------- prefix sharing on quantized pages -----------------------


def test_prefix_hit_exact_on_quantized_shared_pages():
    """Quantize-on-write is deterministic, so a prefix hit over int8 pages
    reproduces the cold run's tokens bit-for-bit and still skips prefill."""
    from repro.quantization.modifier import set_kv_cache_dtype

    cfg = _tiny_lm("paged", num_pages=25)
    set_kv_cache_dtype(cfg, "int8", paged_only=True)
    engine = _engine(cfg, max_len=32, slots=4)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 47, size=(20,))
    gw = ServingGateway(engine, prefill_chunk=8, seed=0)
    # The quantized pool keeps the full serving feature set.
    assert gw.scheduler.prefix is not None
    assert gw.scheduler.manager.pool_dtype == "int8"
    rid = gw.submit(prompt, sampling=SamplingParams(max_new_tokens=8))
    cold = gw.drain()[rid]
    rid = gw.submit(prompt, sampling=SamplingParams(max_new_tokens=8))
    warm = gw.drain()[rid]
    assert warm.tokens == cold.tokens
    s = gw.scheduler.stats
    assert s["prefix_hits"] == 1 and s["prefill_tokens_skipped"] == 16
    assert gw.scheduler.allocator.num_in_use == 0


def test_spec_decoding_stays_enabled_and_exact_on_int8_pool():
    """scale_pool is inside the attention contract: speculation must stay
    on for quantized pools and greedy spec output must match plain greedy."""
    from repro.quantization.modifier import set_kv_cache_dtype

    cfg = _tiny_lm("paged", num_pages=25)
    set_kv_cache_dtype(cfg, "int8", paged_only=True)
    engine = _engine(cfg, max_len=32, slots=4)
    rng = np.random.default_rng(3)
    prompt = np.tile(rng.integers(1, 47, size=(4,)), 4)  # repetitive: drafts
    gw = ServingGateway(engine, prefill_chunk=8, seed=0, spec_k=3,
                        prefix_caching=False)
    assert gw.scheduler.spec_k == 3, "int8 pool must not disable speculation"
    rid = gw.submit(prompt, sampling=SamplingParams(max_new_tokens=8))
    spec = gw.drain()[rid]
    plain = ServingGateway(engine, prefill_chunk=8, seed=0, spec_k=0,
                           prefix_caching=False)
    rid = plain.submit(prompt, sampling=SamplingParams(max_new_tokens=8))
    assert spec.tokens == plain.drain()[rid].tokens


# --------------------- scale-pool atomicity in the manager -------------------


def test_scale_pool_moves_atomically_with_pages():
    """copy_page / extract_pages / insert_pages / reset_pages must treat
    scale rows exactly like KV payload — bitwise, no leaks."""
    from repro.quantization.modifier import set_kv_cache_dtype

    from repro.serving import Scheduler

    cfg = _tiny_lm("paged", num_pages=9, page=4)
    set_kv_cache_dtype(cfg, "int8", paged_only=True)
    engine = _engine(cfg, max_len=16, slots=2)
    sched = Scheduler(engine, prefill_chunk=4, spec_k=0)
    mgr, cache = sched.manager, sched._cache
    names = {i.name for i in mgr._info}
    assert "scale_pool" in names and mgr.pool_dtype == "int8"

    # Cache leaves are scan-stacked (leading layer axis), so page indexing
    # must go through each leaf's page_axis, like the manager itself does.
    def pages(c, leaf_name, idx):
        leaves = jax.tree_util.tree_flatten(c)[0]
        out = []
        for leaf, info in zip(leaves, mgr._info):
            if info.name == leaf_name:
                out.append(np.take(np.asarray(leaf), idx,
                                   axis=info.page_axis))
        assert out, leaf_name
        return out

    # Write distinctive scales into pages 1..3 of every scale_pool leaf.
    def poke(c):
        leaves = jax.tree_util.tree_flatten(c)[0]
        treedef = jax.tree_util.tree_structure(c)
        out = []
        for i, (leaf, info) in enumerate(zip(leaves, mgr._info)):
            if info.name == "scale_pool":
                moved = jnp.moveaxis(leaf, info.page_axis, 0)
                stamp = (jnp.arange(moved[1:4].size, dtype=leaf.dtype)
                         .reshape(moved[1:4].shape) + 2.0 + i)
                leaf = jnp.moveaxis(moved.at[1:4].set(stamp), 0,
                                    info.page_axis)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    cache = poke(cache)
    before_scales = pages(cache, "scale_pool", [1, 2, 3])
    before_k = pages(cache, "k_pool", [1, 2, 3])
    before_v = pages(cache, "v_pool", [1, 2, 3])

    # copy_page (the COW fork): scale rows travel with the payload.
    copied = mgr.copy_page(cache, src=2, dst=5, valid=4)
    for got, want in zip(pages(copied, "scale_pool", [5]),
                         pages(cache, "scale_pool", [2])):
        np.testing.assert_array_equal(got, want)

    # extract -> reset -> insert round-trips bitwise into new physical pages.
    host = mgr.extract_pages(cache, [1, 2, 3])
    wiped = mgr.reset_pages(cache, [1, 2, 3])
    for leaf in pages(wiped, "pos_pool", [1, 2, 3]):
        # reset invalidates recycled pages' positions; stale scale (and KV)
        # rows become unreachable through the mask — same contract as KV.
        np.testing.assert_array_equal(leaf, -np.ones_like(leaf))
    restored = mgr.insert_pages(wiped, [6, 7, 8], host)
    for got, want in zip(pages(restored, "scale_pool", [6, 7, 8]),
                         before_scales):
        np.testing.assert_array_equal(got, want)
    # KV payload moved with the same indices (atomicity).
    for got, want in zip(pages(restored, "k_pool", [6, 7, 8]), before_k):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(pages(restored, "v_pool", [6, 7, 8]), before_v):
        np.testing.assert_array_equal(got, want)


def test_evict_restore_roundtrip_on_quantized_pool():
    """End-to-end leak guard: preemption under pool pressure extracts and
    reinserts quantized pages (scales included) and every request still
    matches the uncontended dense run."""
    from repro.quantization.modifier import set_kv_cache_dtype
    from repro.serving import Scheduler, ServeRequest

    cfg = _tiny_lm("paged", num_pages=1 + 4, page=4)
    set_kv_cache_dtype(cfg, "int8", paged_only=True)
    engine = _engine(cfg, max_len=16, slots=2)
    dense = _engine(_tiny_lm(), max_len=16, slots=2)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 47, size=(6,)) for _ in range(3)]

    sched = Scheduler(engine, prefill_chunk=4, spec_k=0)
    for rid, prompt in enumerate(prompts):
        sched.submit(ServeRequest(request_id=rid, prompt=prompt,
                                  max_new_tokens=8, priority=rid,
                                  arrival_time=0.1 * rid))
    while sched.step():
        pass
    assert sched.stats["preemptions"] > 0, "pool contention never triggered"
    for rid, prompt in enumerate(prompts):
        expect, _ = dense.generate(prompt[None, :], max_new_tokens=8)
        np.testing.assert_array_equal(
            np.asarray(sched.result(rid).tokens), expect[0],
            err_msg=f"request {rid} diverged after eviction on int8 pool")
    assert sched.allocator.num_in_use == 0


# ----------------------------- fp8 training ----------------------------------


def _bf16_cfg(steps):
    from repro.layers.base import bf16_policy
    from repro.trainer.mesh_rules import DtypePolicyModifier

    cfg = _tiny_trainer_cfg(steps=steps)
    return DtypePolicyModifier.default_config().set(
        policy=bf16_policy()).instantiate().apply(cfg)


def test_fp8_train_parity_60_steps():
    """Delayed-scaling fp8 boundaries track the bf16 loss curve within 1%
    relative at 60 steps (the acceptance bound), with fp32 amax histories
    advancing in the (scan-stacked) layer state."""
    from repro.quantization.modifier import QuantizationModifier

    r16 = _bf16_cfg(60).instantiate().run()
    cfg8 = QuantizationModifier.default_config().set(
        fp8=True).instantiate().apply(_bf16_cfg(60))
    r8 = cfg8.instantiate().run()
    l16, l8 = r16["final"]["loss"], r8["final"]["loss"]
    rel = abs(l8 - l16) / l16
    assert rel < 0.01, (l16, l8, rel)
    assert l8 < r8["history"][0]["loss"] * 0.8, "fp8 run did not learn"

    hists = [(p, v) for p, v in _walk(r8["state"]["params"]).items()
             if p.endswith("fp8_amax_history")]
    assert hists, "no amax history params were created"
    for path, v in hists:
        assert v.dtype == jnp.float32, path  # pinned through bf16 policy
        assert float(jnp.max(v)) > 0, f"history never advanced: {path}"


def test_fp8_composes_with_grad_accum():
    """Microbatched fp8: per-microbatch amaxes max-combine (amax semantics)
    and the step still applies one history roll."""
    from repro.quantization.modifier import QuantizationModifier

    cfg = QuantizationModifier.default_config().set(
        fp8=True).instantiate().apply(_bf16_cfg(6))
    cfg.grad_accum_steps = 2
    res = cfg.instantiate().run()
    assert np.isfinite(res["final"]["loss"])
    hists = [v for p, v in _walk(res["state"]["params"]).items()
             if p.endswith("fp8_amax_history")]
    assert hists and all(float(jnp.max(v)) > 0 for v in hists)


def test_state_update_max_combine_under_accum():
    """apply_state_updates folds collected amaxes into params; the accum
    scan combines microbatch updates with max, not mean."""
    from repro.trainer.train_step import apply_state_updates

    params = {"a": {"h": jnp.zeros(3)}, "w": jnp.ones(2)}
    out = apply_state_updates(params, {"a/h": jnp.arange(3.0)})
    np.testing.assert_array_equal(np.asarray(out["a"]["h"]), [0, 1, 2])
    np.testing.assert_array_equal(np.asarray(out["w"]), 1.0)  # untouched
    with pytest.raises(KeyError):
        apply_state_updates(params, {"a/missing": jnp.zeros(1)})


def _walk(d, pre=""):
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(_walk(v, pre + k + "/"))
        else:
            out[pre + k] = v
    return out


# -------------------------- modifier config path -----------------------------


def test_quantization_modifier_w8a8_and_kv_dtype():
    """One modifier flips Linears to QuantizedLinear AND retargets paged
    KV storage — per-arch enablement is pure config."""
    from repro.quantization.modifier import QuantizationModifier

    cfg = _tiny_trainer_cfg(steps=1)
    # Dense model: kv_dtype with paged_only leaves the ring cache alone.
    mod = QuantizationModifier.default_config().set(
        w8a8=True, kv_dtype="int8").instantiate()
    cfg = mod.apply(cfg)

    from repro.core.config import visit_config

    kinds = []
    visit_config(cfg, lambda p, c: kinds.append(type(c).__qualname__))
    assert not any(k == "Linear.Config" for k in kinds), "a Linear survived"
    assert any("QuantizedLinear" in k for k in kinds)
    # Dense attention cfg untouched by the paged-only kv retarget (a dense
    # ring has nowhere to carry scale rows).
    assert cfg.model.decoder.stack.layer.self_attention.kv_cache_dtype \
        is not jnp.int8


def test_fp8_boundary_only_on_linear():
    """The fp8 fake-quant hook fires at Linear boundaries only; the base
    layer and QuantizedLinear (already int8) opt out."""
    from repro.layers.base import BaseLayer
    from repro.layers.basic import Linear
    from repro.quantization.linear import QuantizedLinear

    assert Linear._fp8_boundary is True
    assert BaseLayer._fp8_boundary is False
    assert QuantizedLinear._fp8_boundary is False


# ------------------------------ grep contract --------------------------------


def test_no_dtype_branching_outside_quantization():
    """Low-precision storage dtypes are named ONLY inside the quantization
    subsystem, the memopt subsystem (optimizer *state* dtypes — same
    containment rule, see tests/test_memopt.py for its own contract), and
    the kernel registry's capability tables. Everything else must thread
    precision through config (DtypePolicy / kv_cache_dtype / KVQuantFormat /
    state_dtype), never branch on dtype literals."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    # Dtype spellings only: short *format names* ("int8", "fp8_e4m3")
    # passed to the subsystem's own entry points are the sanctioned API,
    # so string-equality branching on them is what the pattern hunts
    # (`== "int8"`), not the names themselves.
    pattern = re.compile(
        r"jnp\.int8|jnp\.float8|float8_e4m3fn|float8_e5m2"
        r"|==\s*[\"'](?:int8|fp8|float8)|dtype\s*==\s*[\"']")
    allowed = {"quantization", "kernels/registry.py"}
    offenders = []
    for path in sorted(src.rglob("*.py")):
        rel = path.relative_to(src).as_posix()
        if (rel.startswith("quantization/") or rel.startswith("memopt/")
                or rel in allowed):
            continue
        for lineno, line in enumerate(path.read_text().splitlines(), 1):
            if pattern.search(line):
                offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "dtype literals escaped the quantization subsystem:\n"
        + "\n".join(offenders))
