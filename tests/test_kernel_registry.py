"""Kernel registry: capability-based dispatch (op × platform × feature
matrix), rejection-reason errors, explicit-override precedence, and the
compile-count guard proving the memoized dispatch adds no retraces on the
decode/train hot paths."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.module import functional
from repro.kernels import ops, ref
from repro.kernels import registry as reg
from repro.kernels.registry import (
    KernelConfig,
    KernelDispatchError,
    KernelFeatures,
    KernelSpec,
)


def feats(platform="cpu", **kw):
    return KernelFeatures(platform=platform, **kw)


# ------------------------- resolution matrix ---------------------------------


# (op, platform, feature overrides) -> expected backend under "auto".
AUTO_MATRIX = [
    # attention.fwd: pallas on TPU, blockwise elsewhere; ragged/1-token and
    # grad-carrying calls stay capability-routed.
    ("attention.fwd", "cpu", {}, "blockwise"),
    ("attention.fwd", "tpu", {}, "pallas"),
    ("attention.fwd", "gpu", {}, "blockwise"),
    ("attention.fwd", "tpu", {"needs_grad": True}, "pallas"),  # custom_vjp
    ("attention.fwd", "tpu", {"ragged_positions": True}, "blockwise"),
    ("attention.fwd", "tpu", {"single_query": True}, "blockwise"),
    ("attention.fwd", "cpu", {"interpret": True}, "pallas:interpret"),
    ("attention.fwd", "tpu", {"sliding_window": True}, "pallas"),
    # attention.decode: pallas needs a replicated cache; paged stays pallas.
    ("attention.decode", "cpu", {}, "ref"),
    ("attention.decode", "tpu", {}, "pallas"),
    ("attention.decode", "tpu", {"paged": True}, "pallas"),
    ("attention.decode", "tpu", {"replicated_cache": False}, "ref"),
    ("attention.decode", "cpu", {"interpret": True}, "pallas:interpret"),
    # rmsnorm / wkv6: forward-only kernels reject training.
    ("rmsnorm", "tpu", {}, "pallas"),
    ("rmsnorm", "tpu", {"needs_grad": True}, "ref"),
    ("rmsnorm", "cpu", {}, "ref"),
    ("wkv6", "tpu", {}, "pallas"),
    ("wkv6", "tpu", {"needs_grad": True}, "ref"),
    ("wkv6", "cpu", {"interpret": True}, "pallas:interpret"),
    ("wkv6", "gpu", {}, "ref"),
]


@pytest.mark.parametrize("op,platform,overrides,expected", AUTO_MATRIX)
def test_auto_resolution_matrix(op, platform, overrides, expected):
    spec = reg.resolve(op, feats(platform, **overrides))
    assert spec.backend == expected, (op, platform, overrides)


def test_registered_backends_priority_order():
    assert reg.registered_backends("attention.fwd") == [
        "pallas", "pallas:interpret", "blockwise", "ref"]
    assert set(reg.registered_ops()) == {
        "attention.fwd", "attention.decode", "rmsnorm", "wkv6",
        "wkv6.decode"}


# --------------------- rejection reasons / errors ----------------------------


def test_error_lists_every_candidate_with_reason():
    """The debuggability contract: a failed resolve enumerates each
    candidate backend and why it was rejected."""
    with pytest.raises(KernelDispatchError) as e:
        reg.resolve("attention.decode",
                    feats("cpu", replicated_cache=False), backend="pallas")
    msg = str(e.value)
    for backend in reg.registered_backends("attention.decode"):
        assert backend in msg, f"candidate {backend} missing from error"
    assert "requires platform" in msg
    assert "excluded by explicit backend" in msg


def test_error_on_unknown_op_and_backend():
    with pytest.raises(KernelDispatchError, match="registered ops"):
        reg.resolve("attention.bwd", feats())
    with pytest.raises(KernelDispatchError, match="registered backends"):
        reg.resolve("attention.fwd", feats(), backend="cudnn")


def test_sharded_cache_rejection_reason_is_actionable():
    with pytest.raises(KernelDispatchError, match="replicated KV cache"):
        reg.resolve("attention.decode",
                    feats("tpu", replicated_cache=False), backend="pallas")


def test_wkv6_pallas_registered_available_with_fn():
    """The in-tree wkv6 kernel is registered with its function, imported
    unconditionally (no ImportError guard to hide a failure behind)."""
    spec = reg._REGISTRY["wkv6"]["pallas"]
    assert spec.fn is not None


# ------------------------ explicit-override precedence -----------------------


def test_explicit_backend_overrides_auto_priority():
    s = reg.resolve("attention.fwd", feats("cpu"), backend="ref")
    assert s.backend == "ref"


def test_op_overrides_beat_layer_backend():
    cfg = KernelConfig().set(backend="ref",
                             op_overrides={"attention.decode": "blockwise"})
    assert cfg.backend_for("attention.fwd") == "ref"
    assert cfg.backend_for("attention.decode") == "blockwise"


def test_interpret_normalizes_explicit_pallas():
    cfg = KernelConfig().set(backend="pallas", interpret=True)
    assert cfg.backend_for("attention.fwd") == "pallas:interpret"
    cfg2 = KernelConfig().set(backend="pallas")
    assert cfg2.backend_for("attention.fwd") == "pallas"


def test_explicit_waives_heuristics_not_correctness():
    # single_query is a perf heuristic: waived for explicit requests.
    s = reg.resolve("attention.fwd", feats("tpu", single_query=True),
                    backend="pallas")
    assert s.backend == "pallas"
    # ragged positions are a correctness bound: never waived.
    with pytest.raises(KernelDispatchError, match="not provably identical"):
        reg.resolve("attention.fwd", feats("tpu", ragged_positions=True),
                    backend="pallas")


def test_layerwide_backend_falls_back_for_unregistered_ops():
    """A layer-wide backend is a preference across heterogeneous ops: ops
    that don't register it resolve via auto instead of erroring (the old
    impl="blockwise"/"pallas" configs kept decoding through ref)."""
    # attention.decode has no "blockwise" backend -> auto -> ref on CPU.
    cfg = KernelConfig().set(backend="blockwise")
    spec = reg.resolve_backend("attention.decode", feats("cpu"), cfg)
    assert spec.backend == "ref"
    # wkv6.decode is ref-only; layer-wide pallas(:interpret) falls back.
    cfg = KernelConfig().set(backend="pallas", interpret=True)
    spec = reg.resolve_backend("wkv6.decode", feats("cpu"), cfg)
    assert spec.backend == "ref"
    # Per-op overrides name the op: unknown backends there ARE config bugs.
    cfg = KernelConfig().set(op_overrides={"wkv6.decode": "pallas"})
    with pytest.raises(KernelDispatchError, match="registered backends"):
        reg.resolve_backend("wkv6.decode", feats("cpu"), cfg)


def test_rwkv_layerwide_pallas_backend_generates():
    """End-to-end repro of the layer-wide-backend crash: an RWKV mixer with
    kernel backend="pallas" (the documented impl="pallas" migration) must
    still decode — its recurrent step is ref-only."""
    from repro.layers.rwkv import RWKV6TimeMix

    cfg = RWKV6TimeMix.default_config().set(
        name="tm", input_dim=32, head_dim=16, decay_lora_dim=8,
        kernel=KernelConfig().set(backend="pallas", interpret=True,
                                  wkv_chunk_size=4))
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 32)) * 0.1
    cache, _ = functional(layer, state=state, inputs=(1, 8),
                          method="init_states")
    (cache, y), _ = functional(
        layer, state=state, inputs={"state": cache, "x": x},
        method="prefill")
    (cache, y1), _ = functional(
        layer, state=state, inputs={"state": cache, "x_step": x[:, :1]},
        method="extend_step")
    assert np.isfinite(np.asarray(y1)).all()


def test_interpret_backend_never_auto_selected_without_flag():
    s = reg.resolve("attention.decode", feats("cpu"))
    assert s.backend == "ref"
    # But explicitly selectable even with interpret=False.
    s = reg.resolve("attention.decode", feats("cpu"),
                    backend="pallas:interpret")
    assert s.backend == "pallas:interpret"


# ------------------------------ numerics -------------------------------------


def test_dispatched_backends_agree_numerically():
    """Every eligible attention.fwd backend (on this platform) produces the
    same output for the same inputs."""
    B, S, H, D = 2, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    expect = ref.reference_attention(q, k, v)
    for backend in ("ref", "blockwise", "pallas:interpret"):
        out = ops.flash_attention(
            q, k, v, kernel=KernelConfig().set(backend=backend,
                                               blockwise_chunk_size=16))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"backend={backend}")


# ------------------------- dispatch cache / retraces -------------------------


def test_resolve_is_memoized():
    reg.clear_dispatch_cache()
    f = feats("cpu", dtype="bfloat16")
    s1 = reg.resolve("attention.fwd", f)
    stats0 = reg.dispatch_cache_stats()
    for _ in range(100):
        s2 = reg.resolve("attention.fwd", f)
    assert s2 is s1
    stats1 = reg.dispatch_cache_stats()
    assert stats1["hits"] >= stats0["hits"] + 100
    assert stats1["misses"] == stats0["misses"]


def _tiny_attn(S=16, **kernel_kw):
    from repro.layers import MultiheadAttention

    cfg = MultiheadAttention.default_config().set(
        name="a", input_dim=32, num_heads=4, num_kv_heads=2,
        kv_cache_dtype=jnp.float32)
    if kernel_kw:
        cfg.set(kernel=KernelConfig().set(**kernel_kw))
    layer = cfg.instantiate()
    state = layer.initialize_parameters_recursively(jax.random.PRNGKey(0))
    return layer, state


def test_decode_hot_path_compiles_once():
    """Compile-count guard: repeated decode steps through registry dispatch
    reuse ONE compiled program — resolution happens at trace time and the
    memo cache keeps it off the step path."""
    layer, state = _tiny_attn()
    cache, _ = functional(layer, state=state, inputs=(2, 16),
                          method="init_states")

    @jax.jit
    def step(state, cache, x):
        (cache, y), _ = functional(
            layer, state=state, inputs={"state": cache, "x_step": x},
            method="extend_step")
        return cache, y

    x = jnp.ones((2, 1, 32))
    for _ in range(4):
        cache, _ = step(state, cache, x)
    assert step._cache_size() == 1, "decode hot path retraced"


def test_train_hot_path_compiles_once():
    layer, state = _tiny_attn()

    @jax.jit
    def loss_grad(state, x):
        def loss(s):
            out, _ = functional(layer, state=s, inputs=(x,),
                                is_training=True)
            return jnp.sum(out ** 2)

        return jax.grad(loss)(state)

    x = jnp.ones((2, 16, 32))
    for _ in range(3):
        loss_grad(state, x)
    assert loss_grad._cache_size() == 1, "train hot path retraced"


# --------------------------- layer-level contract ----------------------------


def test_no_impl_string_branching_in_layers():
    """Acceptance criterion: no `impl`-string branching remains anywhere in
    src/repro/layers/ — every kernel call site goes through the registry."""
    layers_dir = os.path.join(os.path.dirname(__file__), os.pardir,
                              "src", "repro", "layers", "*.py")
    pattern = re.compile(r"""\bimpl\s*[=!]=|cfg\.impl\b|\bdecode_impl\b"""
                         r"""|\bkernel_interpret\b""")
    offenders = []
    for path in glob.glob(layers_dir):
        for i, line in enumerate(open(path), 1):
            if pattern.search(line):
                offenders.append(f"{os.path.basename(path)}:{i}: {line.strip()}")
    assert not offenders, "\n".join(offenders)


def test_kernel_modifier_is_ten_line_backend_story():
    """The paper's claim, end to end: adding a hypothetical GPU backend is
    one register() call + one mesh rule — zero layer edits."""
    from repro.trainer.mesh_rules import KernelModifier

    calls = []

    def fake_cudnn(q, k, v, *, q_positions, k_positions, causal,
                   sliding_window, logit_softcap, scale, cfg):
        calls.append("cudnn")
        return ref.reference_attention(
            q, k, v, q_positions=q_positions, k_positions=k_positions,
            causal=causal, sliding_window=sliding_window,
            logit_softcap=logit_softcap, scale=scale)

    reg.register(KernelSpec(op="attention.fwd", backend="cudnn",
                            fn=fake_cudnn, platforms=("gpu", "cpu"),
                            priority=80))
    try:
        layer, state = _tiny_attn()
        mod = KernelModifier.default_config().set(
            op_overrides={"attention.fwd": "cudnn"}).instantiate()
        cfg2 = mod.apply(layer.config.clone())
        layer2 = cfg2.instantiate()
        x = jnp.ones((1, 8, 32))
        out2, _ = functional(layer2, state=state, inputs=(x,))
        assert calls == ["cudnn"]
        out1, _ = functional(layer, state=state, inputs=(x,))
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=2e-5, rtol=2e-5)
    finally:
        del reg._REGISTRY["attention.fwd"]["cudnn"]
        reg.clear_dispatch_cache()


def test_bench_kernels_parity_cases_all_match_ref():
    """Every parity case of ``benchmarks/bench_kernels.py`` runs on the CPU
    (Pallas interpreted) and matches the ref oracle: a kernel layout change
    cannot break the benchmark harness unseen."""
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "bench_kernels.py")
    spec = importlib.util.spec_from_file_location("bench_kernels", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench.run()
    parity = bench.LAST_JSON["parity"]
    cases = bench._parity_cases()
    assert {op for op, _, _ in cases} == set(parity)
    assert sum(len(rows) for rows in parity.values()) == len(cases)
    bad = {(op, backend): row for op, rows in parity.items()
           for backend, row in rows.items() if not row["ok"]}
    assert not bad, bad
