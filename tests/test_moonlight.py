"""Moonlight-16B-A3B (the DeepSeek-V3 block) against its plain float32
reference (``chipbench/configs/moonlight.py``) at a tiny size on the CPU:
hidden 64, 4 latent-attention heads (16 + 16 RoPE / 16), latent 32, 8
experts of which 4 held, top-2, 1 shared expert, 1 dense + 2 expert
layers.

Both sides compute in float32 on the CPU, so every gap is summation order
alone: a relative 1e-5 bounds it with room (the readings are near 1e-7).
"""

import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness, program  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.configs.common import ffn_cfg  # noqa: E402
from repro.core.module import functional  # noqa: E402
from repro.layers.moe import DroplessMoE, SigmoidRouter  # noqa: E402

CELL = "moonlight-16b-a3b-train-d6-e8.pretrain-8k"
TINY = dict(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=16, kv_lora_rank=32,
            intermediate_size=128, moe_intermediate_size=32,
            n_shared_experts=1, router_experts=8, n_routed_experts=4,
            first_held_expert=0, num_experts_per_tok=2, num_hidden_layers=3,
            vocab_size=256, global_batch=2, instance_type="cpu-local")
TOL = 1e-5  # float32 on both sides: summation order only


def _ctx(**config):
    args = types.SimpleNamespace(workload=CELL, seed=2**33 + 7, seconds=0.5,
                                 trace=0)
    return harness.prepare(ROOT, args, t_start=time.time(), overrides={
        "config": dict(TINY, **config), "mix": {"seq_len": 64}})


@pytest.fixture(scope="module")
def ref():
    return program.reference(_ctx()["config"])


def _moe(cfg, held):
    """The program's expert layer at ``cfg``'s sizes, holding ``held``."""
    layer = DroplessMoE.default_config().set(
        name="ff", input_dim=cfg["hidden_size"],
        hidden_dim=cfg["moe_intermediate_size"],
        num_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"], held_experts=held)
    layer.shared = ffn_cfg(cfg["n_shared_experts"]
                           * cfg["moe_intermediate_size"])
    layer.router.set(routed_scale=cfg["routed_scaling_factor"])
    return layer.instantiate(parent=None)


def _moe_params(w, held, layer=0):
    """The program's expert-layer tree for ``held`` = (first, count), from
    the reference's weights of expert layer ``layer``."""
    first, count = held
    cut = slice(first, first + count)
    return {
        "router": {"weight": w["w_router"][layer],
                   "bias": w["router_bias"][layer]},
        "wi_0": w["we_up"][layer, cut], "wi_1": w["we_gate"][layer, cut],
        "wo": w["we_down"][layer, cut],
        "shared_experts": {
            "up_proj0": {"weight": w["ws_up"][layer]},
            "up_proj1": {"weight": w["ws_gate"][layer]},
            "down_proj": {"weight": w["ws_down"][layer]}},
    }


def _layer_weights(w, layer=0):
    return {k: v[layer] for k, v in w.items() if k in (
        "w_router", "router_bias", "we_gate", "we_up", "we_down", "ws_gate",
        "ws_up", "ws_down")}


def test_program_matches_reference_through_the_harness():
    """The cell's harness on the CPU: the program's training objective over
    three steps, its first gradient (Adam's first moment) and its change
    over the three steps (the router bias's update included) against the
    plain reference's."""
    ctx = _ctx()
    ctx["peaks"] = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    result = harness.run_cell(ctx, jax.devices()[:1])
    for name, check in result["checks"].items():
        assert check["value"] < TOL, (name, check)
    assert result["failed"] == 0 and result["attempted"] > 0
    held = ctx["records"]["held_assignments"]
    assert held and len(held[0]) == 2  # one counter per expert layer


def test_shares_sum_to_the_uncut_layer(ref):
    """The two disjoint shares (experts 0-3 and 4-7), with the shared expert
    counted once, add up to the reference's layer that holds all 8."""
    cfg = _ctx()["config"]
    full = dict(cfg, n_routed_experts=8)
    w = ref.make_weights(full, 3, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
    want, _ = ref._experts(full, ref._mm("fp32"), _layer_weights(w), x)
    outs = []
    for held in ((0, 4), (4, 4)):
        layer = _moe(cfg, held)
        y, _ = functional(layer, state=_moe_params(w, held), inputs=(x,))
        outs.append(y)
    shared = ref._by_tokens(lambda h: ref._swiglu(
        ref._mm("fp32"), h, w["ws_gate"][0], w["ws_up"][0], w["ws_down"][0]),
        x)
    got = outs[0] + outs[1] - shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=TOL, atol=TOL)


def test_dropless_keeps_every_token(ref):
    """A router that sends every token to experts 0 and 1 (its bias far
    above the scores' range) keeps every token: 2 x tokens held rows, and
    the layer equals the reference's."""
    cfg = _ctx()["config"]
    w = ref.make_weights(cfg, 4, jnp.float32)
    bias = jnp.zeros((8,)).at[0].set(10.0).at[1].set(9.0)
    w = dict(w, router_bias=w["router_bias"].at[0].set(bias))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64), jnp.float32)
    layer = _moe(cfg, (0, 4))
    y, col = functional(layer, state=_moe_params(w, (0, 4)), inputs=(x,))
    assert float(col.summaries["held_assignments"]) == 2 * 2 * 64
    want, _ = ref._experts(cfg, ref._mm("fp32"), _layer_weights(w), x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=TOL,
                               atol=TOL)


def _router(bias):
    router = SigmoidRouter.default_config().set(
        name="router", input_dim=16, num_experts=8, top_k=2,
        routed_scale=2.446, bias_rate=1e-3).instantiate(parent=None)
    weight = jax.random.normal(jax.random.PRNGKey(5), (16, 8)) / 4
    return router, {"weight": weight, "bias": jnp.asarray(bias, jnp.float32)}


def test_bias_moves_selection_not_gates():
    """The bias picks the experts; the gates of a selection are the
    unbiased scores of the experts picked, normalised and scaled."""
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 16))
    router, state = _router(np.zeros(8))
    top0, gates0 = functional(router, state=state, inputs=(x,))[0]
    router, state = _router([0, 0, 0, 0, 0, 0, 5.0, 4.0])
    top1, gates1 = functional(router, state=state, inputs=(x,))[0]
    assert (np.sort(np.asarray(top1), -1) == [6, 7]).all()
    assert not (np.asarray(top0) == np.asarray(top1)).all()
    scores = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, state["weight"]))
    chosen = jnp.take_along_axis(scores, top1, -1)
    np.testing.assert_allclose(
        np.asarray(gates1),
        np.asarray(chosen / chosen.sum(-1, keepdims=True) * 2.446),
        rtol=1e-6)
    # The same selection gives the same gates whatever the bias.
    router, state = _router(np.zeros(8) + 3.0)
    top2, gates2 = functional(router, state=state, inputs=(x,))[0]
    np.testing.assert_array_equal(np.asarray(top2), np.asarray(top0))
    np.testing.assert_allclose(np.asarray(gates2), np.asarray(gates0),
                               rtol=1e-6)


def test_bias_update_rule():
    """In training the router emits b + gamma * sign(mean load - load),
    its loads counted over every expert; in evaluation nothing."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 16))
    bias = np.linspace(-0.01, 0.01, 8)
    router, state = _router(bias)
    (top, _), col = functional(router, state=state, inputs=(x,),
                               is_training=True)
    load = np.bincount(np.asarray(top).ravel(), minlength=8)
    want = bias + 1e-3 * np.sign(load.mean() - load)
    np.testing.assert_allclose(np.asarray(col.state_updates["bias"]), want,
                               rtol=1e-6)
    _, col = functional(router, state=state, inputs=(x,))
    assert not col.state_updates


def test_the_chip_share_is_the_configuration_file():
    """``ArchSpec.make_share`` holds what the benchmark's configuration
    file states (depth, experts held, vocabulary slice, every width): its
    parameters have the reference's shapes leaf for leaf."""
    cfg = harness.prepare(ROOT, types.SimpleNamespace(
        workload=CELL, seed=1, seconds=1, trace=0), t_start=0.0)["config"]
    ref = program.reference(cfg)
    share = registry.get_spec("moonlight-16b-a3b").make_share()
    specs = share.instantiate(parent=None).create_parameter_specs_recursively()
    program.check_layout(specs, cfg, ref)
    assert sum(int(np.prod(s)) for s in ref.leaf_shapes(cfg).values()) \
        == 668_890_432


def test_flops_per_token_of_the_cell(ref):
    """2.64 GFLOP a token at 8k: matmuls 1.88 (routed experts at 6 x 8/64
    assignments a token) and attention 0.75, forward and backward."""
    cfg = harness.prepare(ROOT, types.SimpleNamespace(
        workload=CELL, seed=1, seconds=1, trace=0), t_start=0.0)["config"]
    assert 6 * ref.matmul_params(cfg) == pytest.approx(1.88e9, rel=0.01)
    assert 3 * ref.attention_flops_per_token(cfg, 8192) == pytest.approx(
        0.755e9, rel=0.01)
    assert ref.flops_per_token(cfg, 8192) == pytest.approx(2.64e9, rel=0.01)
