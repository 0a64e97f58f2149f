"""Trainer substrate tests: overfit, grad accum, checkpoint, mesh rules,
dtype policy (mixed precision), ZeRO-1 optimizer-state sharding."""

import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.config import config_for_function
from repro.layers import (
    CausalLM,
    Decoder,
    DtypePolicy,
    Repeat,
    TransformerLayer,
    bf16_policy,
)
from repro.trainer import optimizers as opt_lib
from repro.kernels.registry import KernelConfig
from repro.trainer.mesh_rules import (
    DtypePolicyModifier,
    GradAccumModifier,
    KernelModifier,
    MeshShapeModifier,
    OffloadOptimizerModifier,
    RematPolicyModifier,
    Zero1Modifier,
    apply_mesh_rules,
)
from repro.trainer.trainer import SpmdTrainer, WatchdogTimeout, _Watchdog


def _tiny_trainer_cfg(tmpdir=None, vocab=32, dim=32, L=2, steps=30,
                      batch=8, seq=16):
    layer = TransformerLayer.default_config().set(input_dim=dim)
    layer.self_attention.set(num_heads=4, num_kv_heads=2)
    layer.feed_forward.set(hidden_dim=dim * 2)
    model = CausalLM.default_config().set(
        decoder=Decoder.default_config().set(
            vocab_size=vocab, dim=dim,
            stack=Repeat.default_config().set(layer=layer, num_layers=L,
                                              remat_policy=None)))
    cfg = SpmdTrainer.default_config().set(name="trainer", model=model,
                                           max_steps=steps, log_every_n=5, seed=1)
    cfg.input.set(task="lm", vocab_size=vocab, seq_len=seq, global_batch_size=batch)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
        peak_lr=1e-2, weight_decay=1e-4)
    if tmpdir is not None:
        cfg.checkpointer = Checkpointer.default_config().set(directory=str(tmpdir))
        cfg.checkpoint_every_n = 10
    return cfg


def test_overfit_tiny_lm():
    """Loss must drop substantially on the learnable synthetic stream."""
    cfg = _tiny_trainer_cfg(steps=100)
    trainer = cfg.instantiate()
    result = trainer.run()
    first = result["history"][0]["loss"]
    last = result["final"]["loss"]
    assert np.isfinite(last)
    assert last < first * 0.75, f"no learning: {first} -> {last}"


def test_grad_accum_equivalence():
    """k microbatches of B/k == one batch of B (same grads => same params)."""
    cfg_a = _tiny_trainer_cfg(steps=3, batch=8)
    cfg_b = _tiny_trainer_cfg(steps=3, batch=8)
    cfg_b.grad_accum_steps = 2
    ra = cfg_a.instantiate().run()
    rb = cfg_b.instantiate().run()
    la = jax.tree.leaves(ra["state"]["params"])
    lb = jax.tree.leaves(rb["state"]["params"])
    for a, b in zip(la, lb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_checkpoint_roundtrip_and_gc(tmp_path):
    cfg = _tiny_trainer_cfg(tmpdir=tmp_path, steps=30)
    cfg.checkpointer.keep_last_n = 2
    trainer = cfg.instantiate()
    result = trainer.run()
    ckpt = trainer.checkpointer
    ckpt.wait()
    assert ckpt.latest_step() == 30
    # GC kept only last 2
    step_dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(step_dirs) == 2
    restored = ckpt.restore(like=jax.device_get(result["state"]))
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(result["state"]["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_from_checkpoint(tmp_path):
    cfg = _tiny_trainer_cfg(tmpdir=tmp_path, steps=20)
    t1 = cfg.instantiate()
    t1.run(num_steps=10)
    t1.checkpointer.wait()
    assert t1.checkpointer.latest_step() == 10
    # New trainer resumes from step 10 and continues to 20.
    t2 = cfg.clone().instantiate()
    result = t2.run(num_steps=20)
    assert result["final"]["step"] == 19
    assert int(result["state"]["step"]) == 20


def test_state_shardings_structure():
    cfg = _tiny_trainer_cfg(steps=1)
    trainer = cfg.instantiate()
    state = trainer.init_state()
    shardings = trainer.state_shardings(jax.eval_shape(lambda: state))
    # Same tree structure.
    assert jax.tree.structure(jax.tree.map(lambda x: 0, state)) == \
        jax.tree.structure(jax.tree.map(lambda x: 0, shardings))


def test_mesh_rules_apply_per_target():
    """Paper App. A: per-target config with zero model-code changes."""
    cfg = _tiny_trainer_cfg(steps=1)
    rules = [
        ("tpu-v5e-.*", [
            MeshShapeModifier.default_config().set(
                mesh_shape=(16, 16), mesh_axis_names=("data", "model")),
            RematPolicyModifier.default_config().set(policy="full"),
            KernelModifier.default_config().set(
                op_overrides={"attention.fwd": "pallas"}),
        ]),
        ("cpu-.*", [
            MeshShapeModifier.default_config().set(
                mesh_shape=(1,), mesh_axis_names=("data",)),
            KernelModifier.default_config().set(backend="ref",
                                                interpret=True),
            GradAccumModifier.default_config().set(steps=4),
        ]),
    ]
    tpu_cfg = apply_mesh_rules(cfg.clone(), instance_type="tpu-v5e-256-4", rules=rules)
    assert tpu_cfg.mesh_shape == (16, 16)
    attn_kernel = tpu_cfg.model.decoder.stack.layer.self_attention.kernel
    assert attn_kernel.op_overrides == {"attention.fwd": "pallas"}
    assert tpu_cfg.model.decoder.stack.remat_policy == "full"
    # The one KernelModifier reaches EVERY KernelConfig in the tree, not
    # just attention (rmsnorm/wkv6-calling layers included).
    norm_kernel = tpu_cfg.model.decoder.stack.layer.norm.kernel
    assert norm_kernel.op_overrides == {"attention.fwd": "pallas"}

    cpu_cfg = apply_mesh_rules(cfg.clone(), instance_type="cpu-local", rules=rules)
    assert cpu_cfg.mesh_shape == (1,)
    assert cpu_cfg.grad_accum_steps == 4
    attn_kernel = cpu_cfg.model.decoder.stack.layer.self_attention.kernel
    assert attn_kernel.backend == "ref" and attn_kernel.interpret is True


def test_mesh_rules_modifiers_offload_kernelblock_zero1():
    """Satellite coverage: the remaining one-knob modifiers + the generic
    KernelModifier tiling table."""
    cfg = _tiny_trainer_cfg(steps=1)
    rules = [
        ("tpu-.*", [
            OffloadOptimizerModifier.default_config().set(enabled=True),
            KernelModifier.default_config().set(
                update={"blockwise_chunk_size": 256, "block_q": 512}),
            Zero1Modifier.default_config(),
            GradAccumModifier.default_config().set(steps=2),
        ]),
    ]
    out = apply_mesh_rules(cfg.clone(), instance_type="tpu-v5e-16", rules=rules)
    assert out.offload_optimizer_state is True
    assert out.opt_state_sharding == "zero1"
    assert out.grad_accum_steps == 2
    attn = out.model.decoder.stack.layer.self_attention
    assert attn.kernel.blockwise_chunk_size == 256
    assert attn.kernel.block_q == 512
    # Non-matching instance types leave the config untouched.
    same = apply_mesh_rules(cfg.clone(), instance_type="gpu-H100", rules=rules)
    assert same.opt_state_sharding == "params"
    # Unknown tiling keys fail loudly instead of silently no-opping.
    bad = [("tpu-.*", [KernelModifier.default_config().set(
        update={"blockwzse_chunk": 1})])]
    with pytest.raises(ValueError, match="non-KernelConfig fields"):
        apply_mesh_rules(cfg.clone(), instance_type="tpu-v5e-16", rules=bad)


def test_mesh_rules_fullmatch_not_prefix():
    """Regression (satellite): rules are anchored fullmatch. The old
    ``fullmatch(...) or match(...)`` made every rule a prefix match, so a
    broad rule listed first (e.g. "tpu-.*") shadowed "tpu-v5e-.*" AND a
    non-.* pattern like "tpu-v5e" matched "tpu-v5e-256"."""
    cfg = _tiny_trainer_cfg(steps=1)
    rules = [
        # A pattern without .* must NOT prefix-match longer instance types.
        ("tpu-v5e", [GradAccumModifier.default_config().set(steps=8)]),
        ("tpu-v5e-.*", [GradAccumModifier.default_config().set(steps=2)]),
    ]
    out = apply_mesh_rules(cfg.clone(), instance_type="tpu-v5e-256", rules=rules)
    assert out.grad_accum_steps == 2, \
        "bare 'tpu-v5e' prefix-matched 'tpu-v5e-256'"
    exact = apply_mesh_rules(cfg.clone(), instance_type="tpu-v5e", rules=rules)
    assert exact.grad_accum_steps == 8


def test_dtype_policy_modifier_reaches_every_layer():
    """The paper's ~10-LoC claim for mixed precision: ONE modifier sets the
    policy on every layer config in the tree, and the trainer grad dtype."""
    cfg = _tiny_trainer_cfg(steps=1)
    policy = DtypePolicy().set(compute_dtype=jnp.bfloat16,
                               grad_dtype=jnp.bfloat16)
    mod = DtypePolicyModifier.default_config().set(policy=policy).instantiate()
    cfg = mod.apply(cfg)
    dec = cfg.model.decoder
    for node in (cfg.model, dec, dec.emb, dec.stack, dec.stack.layer,
                 dec.stack.layer.self_attention,
                 dec.stack.layer.self_attention.proj,
                 dec.stack.layer.feed_forward, dec.stack.layer.norm):
        assert node.dtype_policy is not None, node
        assert node.dtype_policy.compute_dtype == jnp.bfloat16
    assert cfg.grad_dtype == jnp.bfloat16


def test_bf16_policy_training_parity():
    """bf16-compute/fp32-master training must track the fp32 loss curve
    (documented tolerance: final loss within 5% after 60 steps) while the
    model actually computes in bf16 (logits dtype check)."""
    from repro.core.module import functional

    def run(policy):
        cfg = _tiny_trainer_cfg(steps=60)
        if policy is not None:
            mod = DtypePolicyModifier.default_config().set(
                policy=policy).instantiate()
            cfg = mod.apply(cfg)
        trainer = cfg.instantiate()
        return trainer, trainer.run()

    _, r32 = run(None)
    tr16, r16 = run(bf16_policy())
    assert all(str(l.dtype) == "float32"
               for l in jax.tree.leaves(r16["state"]["params"]))
    logits, _ = functional(tr16.model, state=jax.device_get(r16["state"]["params"]),
                           inputs=(tr16.input.make_batch(0),), method="predict")
    assert logits.dtype == jnp.bfloat16
    rel = abs(r16["final"]["loss"] - r32["final"]["loss"]) / r32["final"]["loss"]
    assert rel < 0.05, (r32["final"]["loss"], r16["final"]["loss"])
    # Both actually learned.
    assert r16["final"]["loss"] < r16["history"][0]["loss"] * 0.8


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "jamba-1.5-large-398b",
                                  "hubert-xlarge"])
def test_bf16_policy_traces_on_diverse_archs(arch):
    """One DtypePolicyModifier must cover MoE routing, hybrid Mamba blocks
    and the audio MaskedLM without touching any model code: trace the full
    train step (eval_shape, no compile) under the bf16 policy."""
    from repro.configs import registry

    spec = registry.get_spec(arch)
    model_cfg = spec.make_smoke()
    cfg = SpmdTrainer.default_config().set(name="t", model=model_cfg,
                                           max_steps=1)
    task = {"audio": "audio", "vlm": "vlm"}.get(spec.modality, "lm")
    cfg.input.set(task=task, vocab_size=model_cfg.decoder.vocab_size,
                  seq_len=16, global_batch_size=4,
                  model_dim=model_cfg.decoder.dim, num_patches=4)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(peak_lr=1e-3)
    mod = DtypePolicyModifier.default_config().set(
        policy=bf16_policy()).instantiate()
    cfg = mod.apply(cfg)
    trainer = cfg.instantiate()
    state = jax.eval_shape(trainer.init_state)
    batch = {k: jnp.asarray(v) for k, v in trainer.input.make_batch(0).items()}
    new_state, metrics = jax.eval_shape(trainer.make_train_step(), state, batch)
    assert metrics["loss"].dtype == jnp.float32  # loss stays an fp32 island
    # Master params remain fp32 through the update.
    assert all(l.dtype == jnp.float32
               for l in jax.tree.leaves(new_state["params"]))


def test_grad_accum_rejects_indivisible_batch():
    cfg = _tiny_trainer_cfg(steps=1, batch=8)
    cfg.grad_accum_steps = 3  # 8 % 3 != 0
    with pytest.raises(ValueError, match="not divisible by grad_accum_steps"):
        cfg.instantiate().run()


def test_grad_accum_passes_non_array_entries_through():
    """Shared (non-batched) entries like position arrays or python scalars
    must not be microbatch-split (the old code crashed on .reshape)."""
    cfg = _tiny_trainer_cfg(steps=2, batch=8)
    cfg.grad_accum_steps = 2
    trainer = cfg.instantiate()

    step_fn = trainer.make_train_step()
    state = trainer.init_state()
    batch = {k: jnp.asarray(v) for k, v in trainer.input.make_batch(0).items()}
    batch["positions"] = jnp.arange(batch["input_ids"].shape[1])  # (S,) shared
    new_state, metrics = jax.jit(step_fn)(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_grad_accum_accumulates_in_configured_dtype():
    cfg = _tiny_trainer_cfg(steps=1, batch=8)
    cfg.grad_accum_steps = 2
    cfg.grad_dtype = jnp.bfloat16
    trainer = cfg.instantiate()
    # Trace the step: the scan carry (accumulated grads) must be bf16.
    from repro.trainer.train_step import make_grad_fn, make_loss_fn

    loss_fn = make_loss_fn(trainer.model)
    grad_fn = make_grad_fn(loss_fn, grad_accum_steps=2,
                           grad_dtype=jnp.bfloat16)
    state = trainer.init_state()
    batch = {k: jnp.asarray(v) for k, v in trainer.input.make_batch(0).items()}
    _, _, grads = jax.eval_shape(
        grad_fn, state["params"], batch, jax.random.PRNGKey(0))
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(grads))


def test_watchdog_warn_and_raise_modes():
    # warn: records, never raises, never interrupts.
    wd = _Watchdog(0.02, on_timeout="warn")
    wd.beat(0)
    time.sleep(0.08)
    wd.stop()
    assert wd.fired == [0]
    # raise: the timer thread interrupts the (hung) main thread, and
    # check() converts the interrupt into the typed error — this is how a
    # hard-blocked host loop actually surfaces the timeout.
    wd = _Watchdog(0.02, on_timeout="raise")
    interrupted = False
    try:
        wd.beat(3)
        for _ in range(200):  # a "hung step": blocked in the host loop
            time.sleep(0.01)
    except KeyboardInterrupt:
        interrupted = True
    assert interrupted and wd.fired == [3]
    with pytest.raises(WatchdogTimeout, match=r"\[3\]"):
        wd.check()
    with pytest.raises(WatchdogTimeout):  # heartbeat fallback also raises
        wd.beat(4)
    with pytest.raises(ValueError, match="on_timeout"):
        _Watchdog(1.0, on_timeout="explode")


def test_train_step_compiles_once_across_resume(tmp_path):
    """Compile-count regression guard: one trainer instance compiles the
    train step exactly once, including a checkpoint-resume continuation."""
    cfg = _tiny_trainer_cfg(tmpdir=tmp_path, steps=20)
    trainer = cfg.instantiate()
    trainer.run(num_steps=10)
    trainer.checkpointer.wait()
    assert trainer.checkpointer.latest_step() == 10
    result = trainer.run(num_steps=20)  # resumes from step 10
    assert int(result["state"]["step"]) == 20
    assert trainer._jit_step._cache_size() == 1, \
        "train step recompiled across checkpoint resume"


ZERO1_SUBPROCESS = textwrap.dedent("""
    import jax, numpy as np
    from repro.core.config import config_for_function, update_configs_recursively
    from repro.layers import CausalLM, Decoder, Repeat, TransformerLayer
    from repro.trainer import optimizers as opt_lib
    from repro.trainer.trainer import SpmdTrainer

    PART_FIELDS = ["weight_partition", "qkv_weight_partition",
                   "out_weight_partition", "up_weight_partition",
                   "down_weight_partition", "gate_weight_partition"]

    def make(zero1):
        layer = TransformerLayer.default_config().set(input_dim=32)
        layer.self_attention.set(num_heads=4, num_kv_heads=2)
        layer.feed_forward.set(hidden_dim=64)
        model = CausalLM.default_config().set(
            decoder=Decoder.default_config().set(
                vocab_size=32, dim=32,
                stack=Repeat.default_config().set(
                    layer=layer, num_layers=2, remat_policy=None)))
        cfg = SpmdTrainer.default_config().set(
            name="t", model=model, max_steps=2, log_every_n=1, seed=1,
            mesh_shape=(4,), mesh_axis_names=("data",))
        # Pure data parallelism: weights replicated along "data".
        update_configs_recursively(cfg.model, {f: None for f in PART_FIELDS})
        cfg.input.set(task="lm", vocab_size=32, seq_len=16, global_batch_size=8)
        cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(peak_lr=1e-2)
        if zero1:
            cfg.opt_state_sharding = "zero1"
        return cfg

    def per_device_opt_bytes(state, shardings):
        total = 0
        for leaf, sh in zip(jax.tree.leaves(state["opt_state"]),
                            jax.tree.leaves(shardings["opt_state"])):
            total += int(np.prod(sh.shard_shape(leaf.shape))) * leaf.dtype.itemsize
        return total

    out = {}
    for zero1 in (False, True):
        trainer = make(zero1).instantiate()
        res = trainer.run()
        state = res["state"]
        shardings = trainer.state_shardings(jax.eval_shape(lambda: state))
        # Every opt-state leaf must actually LIVE in the declared layout.
        for leaf, sh in zip(jax.tree.leaves(state["opt_state"]),
                            jax.tree.leaves(shardings["opt_state"])):
            assert leaf.sharding == sh, (leaf.shape, leaf.sharding, sh)
        out[zero1] = (per_device_opt_bytes(state, shardings),
                      float(res["final"]["loss"]))
    ratio = out[False][0] / out[True][0]
    assert ratio > 3.0, f"ZeRO-1 saved only {ratio:.2f}x on a 4-way mesh"
    assert abs(out[False][1] - out[True][1]) < 1e-4, out

    # Regression: zero1 with the DEFAULT (FSDP-style, data-axis-using)
    # weight partitions must not produce duplicate-axis PartitionSpecs.
    cfg = make(True)
    layer = TransformerLayer.default_config().set(input_dim=32)
    layer.self_attention.set(num_heads=4, num_kv_heads=2)
    layer.feed_forward.set(hidden_dim=64)
    cfg.model = CausalLM.default_config().set(
        name="model",
        decoder=Decoder.default_config().set(
            vocab_size=32, dim=32,
            stack=Repeat.default_config().set(
                layer=layer, num_layers=2, remat_policy=None)))
    res = cfg.instantiate().run()
    assert np.isfinite(res["final"]["loss"])
    print(f"OK ratio={ratio:.3f}")
""")


def test_zero1_shards_opt_state_on_multidevice_mesh():
    """Per-device optimizer-state bytes shrink ~4x on a 4-device data mesh
    with identical losses. Runs in a subprocess so the forced 4-CPU-device
    topology can't leak into the rest of the suite."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", ZERO1_SUBPROCESS],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK ratio=" in proc.stdout


PER_SHARD_FLASH_SUBPROCESS = textwrap.dedent("""
    import numpy as np
    from repro.core.config import config_for_function
    from repro.layers import CausalLM, Decoder, Repeat, TransformerLayer
    from repro.trainer import optimizers as opt_lib
    from repro.trainer.mesh_rules import KernelModifier
    from repro.trainer.trainer import SpmdTrainer

    def losses(mesh_shape, backend):
        layer = TransformerLayer.default_config().set(input_dim=32)
        layer.self_attention.set(num_heads=4, num_kv_heads=2)
        layer.feed_forward.set(hidden_dim=64)
        model = CausalLM.default_config().set(
            decoder=Decoder.default_config().set(
                vocab_size=32, dim=32,
                stack=Repeat.default_config().set(
                    layer=layer, num_layers=2, remat_policy="full")))
        cfg = SpmdTrainer.default_config().set(
            name="t", model=model, max_steps=3, log_every_n=1, seed=1,
            mesh_shape=mesh_shape, mesh_axis_names=("data", "model"),
            opt_state_sharding="zero1")
        cfg.input.set(task="lm", vocab_size=32, seq_len=16,
                      global_batch_size=8)
        cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
            peak_lr=1e-2)
        cfg = KernelModifier.default_config().set(
            op_overrides={"attention.fwd": backend}, interpret=True,
            update={"block_q": 8, "block_k": 8}).instantiate().apply(cfg)
        return [row["loss"] for row in cfg.instantiate().run()["history"]]

    ref = losses((1, 1), "ref")
    for shape in [(4, 1), (2, 2), (-1, 1)]:
        np.testing.assert_allclose(losses(shape, "pallas"), ref, rtol=1e-4)

    # A batch or head count the mesh does not divide raises; it is never
    # silently replicated onto every device. So does a split sequence.
    import jax.numpy as jnp
    from repro.core.utils import make_mesh, set_mesh
    from repro.kernels import ops
    from repro.kernels.registry import KernelConfig

    kc = KernelConfig().set(op_overrides={"attention.fwd": "pallas"},
                            interpret=True, block_q=8, block_k=8)
    layer_partition = (("pod", "data"), None, "model", None)
    cases = [((4, 1), (2, 4, 2), layer_partition, "divide"),
             ((2, 2), (4, 3, 3), layer_partition, "divide"),
             ((2, 2), (4, 4, 2), ("data", "model", None, None), "whole")]
    for mesh_shape, (b, hq, hkv), partition, why in cases:
        q = jnp.ones((b, 16, hq, 8))
        kv = jnp.ones((b, 16, hkv, 8))
        with set_mesh(make_mesh(mesh_shape, ("data", "model"))):
            try:
                ops.flash_attention(q, kv, kv, kernel=kc,
                                    partition=partition)
            except ValueError as e:
                assert why in str(e), e
            else:
                raise AssertionError(f"{mesh_shape} {q.shape} ran")
    print("OK")
""")


def test_flash_attention_runs_per_shard_on_multidevice_mesh():
    """GSPMD cannot partition a Pallas kernel: on a multi-device mesh the
    flash-attention call runs per shard under the layer's partition (batch
    over "data", heads over "model"), training matches the reference on one
    device, and a split the kernel cannot take raises. Runs in a subprocess
    with 4 CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", PER_SHARD_FLASH_SUBPROCESS],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_zero1_partition_spec_never_duplicates_axes():
    """Regression: a param already sharded over 'data' on one dim must not
    get 'data' again on a replicated dim (duplicate mesh axes crash
    NamedSharding for every MoE/FSDP-style param)."""
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec

    from repro.layers import ParameterSpec as PSpec
    from repro.trainer.train_step import zero1_partition_spec

    mesh = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 4, "model": 2})
    # Router-gate style ('data', None): dim1 divisible but data already used.
    spec = PSpec(shape=(8, 4), mesh_axes=("data", None))
    assert zero1_partition_spec(spec, mesh) == PartitionSpec("data", None)
    # Expert style ('model', ('pod','data'), None, None): nothing addable.
    spec = PSpec(shape=(2, 8, 16, 4),
                 mesh_axes=("model", ("pod", "data"), None, None))
    assert zero1_partition_spec(spec, mesh) == \
        PartitionSpec("model", "data", None, None)
    # Fully replicated param gets the data axes exactly once.
    spec = PSpec(shape=(8, 4), mesh_axes=None)
    assert zero1_partition_spec(spec, mesh) == PartitionSpec("data", None)
    # 'model'-only param: first divisible replicated dim picks up data.
    spec = PSpec(shape=(6, 8), mesh_axes=(None, "model"))
    assert zero1_partition_spec(spec, mesh) == PartitionSpec(None, "model")
    spec = PSpec(shape=(8, 6), mesh_axes=(None, "model"))
    assert zero1_partition_spec(spec, mesh) == PartitionSpec("data", "model")


def test_master_weights_make_bf16_param_storage_trainable():
    """fp32 master weights in the optimizer state: repeated updates smaller
    than one bf16 ulp must still accumulate (they vanish without masters)."""
    p = {"w": jnp.full((4,), 256.0, jnp.bfloat16)}  # ulp(256) = 2 in bf16
    g = {"w": jnp.full((4,), 1.0, jnp.float32)}
    naive = opt_lib.sgd(learning_rate=0.25)
    master = opt_lib.with_master_weights(opt_lib.sgd(learning_rate=0.25))

    def run(tx):
        params = dict(p)
        state = tx.init(params)
        for _ in range(8):  # 8 * 0.25 = 2.0 total
            updates, state = tx.update(g, state, params)
            params = {"w": (params["w"].astype(jnp.float32)
                            + updates["w"]).astype(jnp.bfloat16)}
        return float(params["w"][0])

    assert run(naive) == 256.0  # each -0.25 step rounds away: stalled
    assert run(master) == 254.0  # masters accumulate, then round
    # adamw grows the wrapper from config.
    tx = opt_lib.adamw(peak_lr=0.1, master_weight_dtype=jnp.float32)
    state = tx.init(p)
    assert isinstance(state, opt_lib.MasterWeightState)
    assert state.master["w"].dtype == jnp.float32


def test_optimizer_unit_behaviour():
    params = {"w": jnp.ones((4,)), "b": jnp.zeros((2,))}
    grads = {"w": jnp.full((4,), 2.0), "b": jnp.ones((2,))}
    tx = opt_lib.adamw(peak_lr=0.1, weight_decay=0.0, max_grad_norm=None)
    state = tx.init(params)
    updates, state = tx.update(grads, state, params)
    # First Adam step: update = -lr * sign-ish(grad).
    np.testing.assert_allclose(np.asarray(updates["w"]),
                               -0.1 * np.ones(4), rtol=1e-3)


def test_clip_by_global_norm():
    grads = {"w": jnp.full((4,), 10.0)}
    tx = opt_lib.clip_by_global_norm(1.0)
    out, _ = tx.update(grads, tx.init(grads), None)
    np.testing.assert_allclose(float(opt_lib.global_norm(out)), 1.0, rtol=1e-5)


def test_lr_schedule_shape():
    sched = opt_lib.linear_warmup_cosine(peak_lr=1.0, warmup_steps=10, total_steps=100)
    assert float(sched(jnp.asarray(0))) == 0.0
    np.testing.assert_allclose(float(sched(jnp.asarray(10))), 1.0, rtol=1e-5)
    assert float(sched(jnp.asarray(100))) < 0.15
    assert float(sched(jnp.asarray(5))) == pytest.approx(0.5, rel=1e-5)
