"""Memory-frugal training subsystem tests (src/repro/memopt/).

Covers the three pillars — factored second moments (Adafactor / SM3),
quantized Adam EMA storage (``adamw(state_dtype=...)``), reversible
residual stacks — plus the MemoryModifier/mesh-rule wiring, the exact
state-bytes accounting, ZeRO-1 composition (subprocess, forced 4-device
mesh), and the subsystem's own grep contract (state-dtype name
interpretation must not leak out of memopt/).
"""

import os
import pathlib
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import config_for_function
from repro.core.module import functional
from repro.layers import (
    CausalLM,
    Decoder,
    FeedForward,
    Repeat,
    TransformerLayer,
)
from repro.memopt import (
    accounting,
    factored,
    state_quant,
)
from repro.memopt.modifier import MemoryModifier
from repro.memopt.reversible import rev_stack, validate_reversible
from repro.trainer import optimizers as opt_lib
from repro.trainer.trainer import SpmdTrainer

# Toy param tree: one stacked matrix (factorable) + one bias (not).
_TOY_PARAMS = {
    "w": jnp.zeros((4, 64, 32), jnp.float32),
    "b": jnp.zeros((32,), jnp.float32),
}


def _opt_state_bytes(tx, params=_TOY_PARAMS):
    return accounting.state_bytes(tx.init(params))


# ------------------------- state bytes / accounting --------------------------


def test_state_bytes_ratios():
    """The headline memory numbers, measured on real init states: bf16
    halves, int8 quarters (minus scales) the Adam EMA bytes; factored
    optimizers drop them by orders of magnitude."""
    base = _opt_state_bytes(opt_lib.adamw())
    bf16 = _opt_state_bytes(opt_lib.adamw(state_dtype="bf16"))
    int8 = _opt_state_bytes(opt_lib.adamw(state_dtype="int8"))
    ada = _opt_state_bytes(factored.adafactor())
    sm3 = _opt_state_bytes(factored.sm3())
    assert base / bf16 >= 1.9, (base, bf16)
    assert base / int8 >= 3.0, (base, int8)
    assert base / ada >= 3.0, (base, ada)
    assert base / sm3 >= 3.0, (base, sm3)
    # fp32 by name is exactly the legacy layout.
    assert _opt_state_bytes(opt_lib.adamw(state_dtype="fp32")) == base


def test_per_leaf_state_bytes():
    per_leaf = accounting.per_leaf_state_bytes(
        opt_lib.adamw().init(_TOY_PARAMS))
    assert sum(per_leaf.values()) == _opt_state_bytes(opt_lib.adamw())
    assert all(isinstance(k, str) and v > 0 for k, v in per_leaf.items())


def test_accounting_works_on_shape_structs():
    """The trainer accounts on eval_shape output (no buffers materialized)."""
    tx = opt_lib.adamw(state_dtype="int8")
    shapes = jax.eval_shape(tx.init, _TOY_PARAMS)
    assert accounting.state_bytes(shapes) == _opt_state_bytes(tx)


# --------------------------- factored optimizers -----------------------------


def test_adafactor_state_shapes():
    state = factored.scale_by_factored_rms().init(_TOY_PARAMS)
    # Flattened leaf order: b (0), w (1). w factors into row/col EMAs with
    # the stacked leading axis kept as a batch dim; b keeps a full moment.
    assert state.v_row["0001"].shape == (4, 64)
    assert state.v_col["0001"].shape == (4, 32)
    assert state.v_full["0000"].shape == (32,)
    assert "0000" not in state.v_row


def test_sm3_state_shapes():
    state = factored.scale_by_sm3().init(_TOY_PARAMS)
    accs_w = state.accumulators["0001"]
    assert {k: v.shape for k, v in accs_w.items()} == {
        "0": (4,), "1": (64,), "2": (32,)}
    assert state.accumulators["0000"]["0"].shape == (32,)


@pytest.mark.parametrize("name,make", [
    ("adamw", lambda: opt_lib.adamw(peak_lr=0.05)),
    ("adamw-bf16", lambda: opt_lib.adamw(peak_lr=0.05, state_dtype="bf16")),
    ("adamw-int8", lambda: opt_lib.adamw(peak_lr=0.05, state_dtype="int8")),
    ("adafactor", lambda: factored.adafactor(peak_lr=0.3)),
    ("sm3", lambda: factored.sm3(peak_lr=0.5)),
])
def test_optimizer_reduces_quadratic_loss(name, make):
    """Every memopt optimizer actually optimizes (shared quadratic)."""
    target = jax.random.normal(jax.random.PRNGKey(0), (16, 16))
    params = {"w": jnp.zeros((16, 16), jnp.float32)}
    loss_fn = lambda p: jnp.mean((p["w"] - target) ** 2)  # noqa: E731
    tx = make()
    state = tx.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, state = tx.update(grads, state, params)
        return jax.tree.map(jnp.add, params, updates), state, loss

    first = None
    for _ in range(60):
        params, state, loss = step(params, state)
        first = loss if first is None else first
    assert float(loss) < 0.5 * float(first), (name, first, loss)


def test_int8_adam_first_step_matches_fp32():
    """Quantization error enters only through the *carried* state: from a
    zero state, the int8 transform's first update is bit-for-bit the fp32
    Adam update (EMA math runs fp32 on freshly dequantized values)."""
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (8, 16)),
             "b": jax.random.normal(jax.random.PRNGKey(2), (16,))}
    params = jax.tree.map(jnp.zeros_like, grads)
    ref = opt_lib.scale_by_adam()
    q = state_quant.scale_by_adam_int8()
    u_ref, _ = ref.update(grads, ref.init(params), params)
    u_q, _ = q.update(grads, q.init(params), params)
    for k in grads:
        np.testing.assert_allclose(u_q[k], u_ref[k], atol=1e-6)


def test_int8_adam_converges_with_quantization_drag():
    """int8 moments optimize the same quadratic, slower: per-row symmetric
    quantization zeroes sub-resolution moment entries, which a tiny
    deterministic quadratic amplifies far more than real training. The
    contract here: steady convergence, bounded drag."""
    target = jax.random.normal(jax.random.PRNGKey(0), (16, 16))
    loss_fn = lambda p: jnp.mean((p["w"] - target) ** 2)  # noqa: E731
    losses = {}
    initial = float(loss_fn({"w": jnp.zeros((16, 16), jnp.float32)}))
    for name, tx in [("fp32", opt_lib.adamw(peak_lr=0.05)),
                     ("int8", opt_lib.adamw(peak_lr=0.05,
                                            state_dtype="int8"))]:
        params = {"w": jnp.zeros((16, 16), jnp.float32)}
        state = tx.init(params)
        for _ in range(60):
            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, state = tx.update(grads, state, params)
            params = jax.tree.map(jnp.add, params, updates)
        losses[name] = float(loss)
    assert losses["fp32"] < 0.01 * initial, losses
    assert losses["int8"] < 0.25 * initial, losses


def test_resolve_state_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="state_dtype"):
        state_quant.resolve_state_dtype("fp4")


def test_master_weights_compose_with_quantized_state():
    """bf16 params + fp32 masters + int8 moments: the full mixed-precision
    memory recipe in one optimizer config."""
    tx = opt_lib.adamw(peak_lr=0.05, state_dtype="int8",
                       master_weight_dtype=jnp.float32)
    params = {"w": jnp.zeros((8, 16), jnp.bfloat16)}
    state = tx.init(params)
    assert isinstance(state, opt_lib.MasterWeightState)
    assert state.master["w"].dtype == jnp.float32
    grads = {"w": jnp.ones((8, 16), jnp.bfloat16)}
    updates, state = tx.update(grads, state, params)
    params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype),
                          params, updates)
    assert jnp.all(jnp.isfinite(params["w"].astype(jnp.float32)))
    # Int8 moments live inside the wrapped chain state.
    int8_leaves = [l for l in jax.tree.leaves(state)
                   if l.dtype == jnp.int8]
    assert int8_leaves, "no quantized moment buffers in the state"


# ----------------------------- chain validation ------------------------------


def test_chain_rejects_wrong_state_arity():
    tx = opt_lib.chain(opt_lib.scale_by_adam(), opt_lib.scale(1.0))
    params = {"w": jnp.zeros((4,))}
    state = tx.init(params)
    with pytest.raises(ValueError, match="chain\\(\\) of 2 transforms"):
        tx.update(params, state[:1], params)
    with pytest.raises(ValueError, match="chain\\(\\) of 2 transforms"):
        tx.update(params, {"not": "a tuple"}, params)


def test_chain_rejects_foreign_state_structure():
    """Restoring an adafactor checkpoint into an adamw chain must fail with
    a config-mismatch message, not a deep tree-map structure error."""
    params = {"w": jnp.zeros((16, 16))}
    adam = opt_lib.adamw()
    ada = factored.adafactor()
    with pytest.raises(ValueError, match="different optimizer config"):
        adam.update(params, ada.init(params), params)


# ------------------------------- reversible ----------------------------------


def _layer_cfg(dim=32):
    layer = TransformerLayer.default_config().set(input_dim=dim)
    layer.self_attention.set(num_heads=4, num_kv_heads=2)
    layer.feed_forward.set(hidden_dim=2 * dim)
    return layer


def _rev_repeat(num_layers=2, dim=32, **kw):
    return Repeat.default_config().set(
        name="stack", layer=_layer_cfg(dim), num_layers=num_layers,
        remat_policy=None, reversible=True, **kw)


def test_rev_stack_inverts_and_matches_autodiff():
    rep = _rev_repeat().instantiate()
    state = rep.initialize_parameters_recursively(jax.random.PRNGKey(0))
    stacked = state["layer"]
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    pos = jnp.arange(8)[None, :].repeat(2, axis=0)

    def run(params, x1, x2, use_custom_vjp):
        y1, y2 = rev_stack(rep.layer, params, x1, x2, pos,
                           is_training=False,
                           use_custom_vjp=use_custom_vjp)
        return jnp.sum(jnp.cos(y1) + jnp.sin(y2))

    val_c, grads_c = jax.value_and_grad(run, argnums=(0, 1, 2))(
        stacked, x, x, True)
    val_r, grads_r = jax.value_and_grad(run, argnums=(0, 1, 2))(
        stacked, x, x, False)
    np.testing.assert_allclose(val_c, val_r, rtol=1e-5)
    for gc, gr in zip(jax.tree.leaves(grads_c), jax.tree.leaves(grads_r)):
        # fp32 + one extra residual-add rounding per inverted layer.
        np.testing.assert_allclose(gc, gr, rtol=5e-3, atol=5e-5)

    # Explicit inversion: reconstruct the inputs from the outputs alone.
    y1, y2 = rev_stack(rep.layer, stacked, x, x, pos, is_training=False)
    h1, h2 = y1, y2
    for i in reversed(range(2)):
        p_i = jax.tree.map(lambda a: a[i], stacked)

        def branch(method, h):
            inputs = {"x": h}
            if method == "attn_branch":
                inputs["positions"] = pos
            out, _ = functional(rep.layer, state=p_i, inputs=inputs,
                                prng_key=None, is_training=False,
                                method=method)
            return out

        h2 = h2 - branch("ffn_branch", h1)
        h1 = h1 - branch("attn_branch", h2)
    np.testing.assert_allclose(h1, x, atol=5e-5)
    np.testing.assert_allclose(h2, x, atol=5e-5)


def test_reversible_repeat_forward_runs_and_differs_from_plain():
    rep = _rev_repeat().instantiate()
    state = rep.initialize_parameters_recursively(jax.random.PRNGKey(0))
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
    out, _ = functional(rep, state=state, inputs=(x,), is_training=False)
    assert out.shape == x.shape
    assert jnp.all(jnp.isfinite(out))
    plain = _rev_repeat().set(reversible=False).instantiate()
    out_plain, _ = functional(plain, state=state, inputs=(x,),
                              is_training=False)
    # Same weights, different (two-stream) computation graph.
    assert not np.allclose(out, out_plain)


def test_reversible_rejects_residual_dropout():
    cfg = _rev_repeat()
    cfg.layer.set(residual_dropout=0.1)
    with pytest.raises(ValueError, match="residual_dropout"):
        cfg.instantiate()


def test_reversible_rejects_non_decomposable_layer():
    ffn = FeedForward.default_config().set(input_dim=32, hidden_dim=64)
    cfg = Repeat.default_config().set(
        name="stack", layer=ffn, num_layers=2, remat_policy=None,
        reversible=True)
    with pytest.raises(ValueError, match="attn_branch"):
        cfg.instantiate()
    # The same layout is fine when not reversible.
    validate_reversible(_rev_repeat().instantiate().layer)


def test_reversible_decode_interface_raises():
    rep = _rev_repeat().instantiate()
    state = rep.initialize_parameters_recursively(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="reversible"):
        functional(rep, state=state, inputs=(2, 8), is_training=False,
                   method="init_states")


# ------------------------- MemoryModifier / mesh rules -----------------------


def _tiny_trainer_cfg(*, steps=4, zero1=True):
    model = CausalLM.default_config().set(
        decoder=Decoder.default_config().set(
            vocab_size=32, dim=32,
            stack=Repeat.default_config().set(
                layer=_layer_cfg(32), num_layers=2, remat_policy=None)))
    cfg = SpmdTrainer.default_config().set(
        name="t", model=model, max_steps=steps, log_every_n=steps, seed=0)
    cfg.input.set(task="lm", vocab_size=32, seq_len=16, global_batch_size=4)
    cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
        peak_lr=1e-2, weight_decay=0.01)
    if zero1:
        cfg.opt_state_sharding = "zero1"
    return cfg


def _apply(cfg, **kw):
    return MemoryModifier.default_config().set(**kw).instantiate().apply(cfg)


def test_memory_modifier_swaps_optimizer_and_carries_tuning():
    cfg = _apply(_tiny_trainer_cfg(), optimizer="adafactor")
    opt = cfg.learner.optimizer
    assert type(opt)._fn is factored.adafactor
    # Experiment tuning (LR, decay) survives the swap; memory knobs change.
    assert opt.peak_lr == 1e-2
    assert opt.weight_decay == 0.01


def test_memory_modifier_state_dtype_and_reversible():
    cfg = _apply(_tiny_trainer_cfg(), state_dtype="bf16", reversible=True)
    assert cfg.learner.optimizer.state_dtype == "bf16"
    assert cfg.model.decoder.stack.reversible is True


def test_memory_modifier_rejects_state_dtype_on_factored():
    cfg = _apply(_tiny_trainer_cfg(), optimizer="sm3")
    with pytest.raises(ValueError, match="state_dtype"):
        _apply(cfg, state_dtype="int8")


def test_memory_modifier_rejects_unknown_optimizer():
    with pytest.raises(ValueError, match="adafactor"):
        _apply(_tiny_trainer_cfg(), optimizer="lion")


def test_frugal_mesh_rules_compose_the_recipe():
    """One instance-type suffix turns on the whole memory-frugal recipe at
    config level (zero model-code changes, paper §4.2)."""
    from repro.launch.train import MESH_RULES
    from repro.trainer.mesh_rules import apply_mesh_rules

    cfg = apply_mesh_rules(_tiny_trainer_cfg(),
                           instance_type="tpu-v5e-256-frugal",
                           rules=MESH_RULES)
    assert cfg.learner.optimizer.state_dtype == "bf16"
    assert cfg.model.decoder.stack.reversible is True
    assert cfg.opt_state_sharding == "zero1"

    cfg = apply_mesh_rules(_tiny_trainer_cfg(),
                           instance_type="tpu-v5e-256-frugal-max",
                           rules=MESH_RULES)
    assert type(cfg.learner.optimizer)._fn is factored.adafactor
    assert cfg.model.decoder.stack.reversible is True


# ----------------------- trainer integration (compile) -----------------------


@pytest.mark.heavy
@pytest.mark.parametrize("memopt", [
    {"state_dtype": "bf16"},
    {"state_dtype": "int8"},
    {"optimizer": "adafactor"},
    {"optimizer": "sm3"},
    {"reversible": True},
])
def test_trainer_memopt_zero1_compiles_once(memopt):
    """Each memopt axis composes with ZeRO-1 end to end: the trainer runs,
    loss is finite, the exported opt-state accounting matches an
    independent eval_shape measurement, and the train step compiles exactly
    once (no retraces from the quantize/requantize or custom_vjp paths)."""
    cfg = _apply(_tiny_trainer_cfg(steps=4), **memopt)
    trainer = cfg.instantiate()
    result = trainer.run()
    assert np.isfinite(result["final"]["loss"])
    expected = accounting.state_bytes(
        jax.eval_shape(trainer.init_state)["opt_state"])
    assert result["opt_state_bytes"] == expected
    assert trainer._jit_step._cache_size() == 1, \
        f"memopt={memopt} retraced the train step"


# ----------------- ZeRO-1 x master weights x quantized state -----------------


MEMOPT_ZERO1_SUBPROCESS = textwrap.dedent("""
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.core.config import config_for_function, update_configs_recursively
    from repro.layers import CausalLM, Decoder, Repeat, TransformerLayer
    from repro.trainer import optimizers as opt_lib
    from repro.trainer.trainer import SpmdTrainer

    PART_FIELDS = ["weight_partition", "qkv_weight_partition",
                   "out_weight_partition", "up_weight_partition",
                   "down_weight_partition", "gate_weight_partition"]

    def make(state_dtype):
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
        update_configs_recursively(cfg.model, {f: None for f in PART_FIELDS})
        cfg.input.set(task="lm", vocab_size=32, seq_len=16, global_batch_size=8)
        cfg.learner.optimizer = config_for_function(opt_lib.adamw).set(
            peak_lr=1e-2, state_dtype=state_dtype,
            master_weight_dtype=jnp.float32)
        cfg.opt_state_sharding = "zero1"
        return cfg

    def per_device_opt_bytes(state, shardings):
        total = 0
        for leaf, sh in zip(jax.tree.leaves(state["opt_state"]),
                            jax.tree.leaves(shardings["opt_state"])):
            total += int(np.prod(sh.shard_shape(leaf.shape))) * leaf.dtype.itemsize
        return total

    out = {}
    for state_dtype in ("fp32", "bf16", "int8"):
        trainer = make(state_dtype).instantiate()
        res = trainer.run()
        state = res["state"]
        shardings = trainer.state_shardings(jax.eval_shape(lambda: state))
        for leaf, sh in zip(jax.tree.leaves(state["opt_state"]),
                            jax.tree.leaves(shardings["opt_state"])):
            assert leaf.sharding == sh, (leaf.shape, leaf.sharding, sh)
        if state_dtype == "int8":
            # The quantized EMA leaves themselves must be ZeRO-1 sharded
            # (param-structured trees keep the data-axis layout) ...
            q = [l for l in jax.tree.leaves(state["opt_state"])
                 if l.dtype == jnp.int8]
            assert q, "no int8 moment leaves in the optimizer state"
            q_total = sum(l.size for l in q)
            q_dev = sum(int(np.prod(l.sharding.shard_shape(l.shape)))
                        for l in q)
            assert q_total / q_dev > 2.0, (q_total, q_dev)
            # ... while the fp32 scale dicts (non-param-structured) stay
            # replicated — tiny, and structurally unshardable by zero1.
            scales = [l for l in jax.tree.leaves(state["opt_state"])
                      if l.dtype == jnp.float32 and l.ndim >= 1
                      and l.shape[-1:] == (1,)]
            assert scales, "no per-row scale leaves found"
            for l in scales:
                assert l.sharding.shard_shape(l.shape) == l.shape
        out[state_dtype] = (per_device_opt_bytes(state, shardings),
                            float(res["final"]["loss"]))
    # fp32: mu+nu+master = 12B/param sharded; bf16: 8B; int8: ~6B + scales.
    r_bf16 = out["fp32"][0] / out["bf16"][0]
    r_int8 = out["fp32"][0] / out["int8"][0]
    assert 1.3 < r_bf16 < 1.7, (out, r_bf16)
    assert r_int8 > 1.5, (out, r_int8)
    rel = abs(out["bf16"][1] - out["fp32"][1]) / max(abs(out["fp32"][1]), 1e-9)
    assert rel < 0.05, out
    print(f"OK r_bf16={r_bf16:.3f} r_int8={r_int8:.3f}")
""")


@pytest.mark.heavy(timeout=420)
def test_zero1_master_weights_quantized_state_sharding():
    """ZeRO-1 x fp32 masters x bf16/int8 moments on a forced 4-CPU-device
    mesh: quantized EMA leaves stay data-sharded, scales stay replicated,
    and per-device optimizer bytes drop by the dtype-arithmetic factors.
    Subprocess so the forced topology can't leak into the suite."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", MEMOPT_ZERO1_SUBPROCESS],
                          env=env, capture_output=True, text=True,
                          timeout=360)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK r_bf16=" in proc.stdout


# ------------------------------ grep contract --------------------------------


def test_state_dtype_names_confined_to_memopt():
    """Optimizer state-dtype *names* are config surface everywhere, but
    their interpretation (name -> storage dtype / quantized layout) lives
    ONLY in repro.memopt. Mirrors the quantization grep contract."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    pattern = re.compile(
        r"state_dtype\s*==|state_dtype\s+in\s|resolve_state_dtype\(")
    offenders = []
    for path in sorted(src.rglob("*.py")):
        rel = path.relative_to(src).as_posix()
        if rel.startswith("memopt/"):
            continue
        for lineno, line in enumerate(path.read_text().splitlines(), 1):
            if pattern.search(line):
                offenders.append(f"{rel}:{lineno}: {line.strip()}")
    assert not offenders, (
        "state-dtype interpretation escaped the memopt subsystem:\n"
        + "\n".join(offenders))
