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
from repro.kernels import ops as kernel_ops  # noqa: E402
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


# ------------------------------------------- routing over held rows only


def _whole_sort_sequence(layer, weights, x, local, gates):
    """``DroplessMoE._sequence`` as it was before the chunked dispatch and
    combine: every one of the S*K sorted rows gathered, masked, gathered
    back and summed in f32 (the oracle)."""
    cfg = layer.config
    count = cfg.held_experts[1]
    S, K = local.shape
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
    held = (jnp.arange(S * K) < jnp.sum(sizes))[:, None]
    rows = jnp.where(held, x[order // K], 0)

    def gmm(lhs, name):
        out = kernel_ops.grouped_matmul(lhs, weights[name], sizes,
                                        kernel=layer.kernel_config)
        return jnp.where(held, out, 0)

    out = gmm(gmm(rows, "wi_0") * jax.nn.silu(gmm(rows, "wi_1")), "wo")
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(S * K, dtype=order.dtype))
    gates = jnp.where(local < count, gates, 0.0)
    y = jnp.einsum("skd,sk->sd",
                   out[back].reshape(S, K, -1).astype(jnp.float32), gates)
    return y, sizes


def _expert_layer(D, H, E, count, K):
    return DroplessMoE.default_config().set(
        name="ff", input_dim=D, hidden_dim=H, num_experts=E, top_k=K,
        held_experts=(0, count)).instantiate(parent=None)


# (input_dim, hidden_dim, experts, held, top_k, tokens, share of the
# assignments routed to held experts; None: a top-k of distinct experts)
ROUTINGS = {
    "none_held": (16, 32, 8, 4, 2, 1024, 0.0),
    "all_held": (16, 32, 8, 4, 2, 1024, 1.0),
    "held_not_a_chunk_multiple": (16, 32, 8, 4, 2, 1024, 0.4),
    "moonlight_widths": (2048, 1408, 64, 8, 6, 128, None),
}


@pytest.mark.parametrize("case", sorted(ROUTINGS))
def test_chunked_routing_matches_the_whole_sort(case):
    """The chunked dispatch and combine give the whole sort's value and its
    gradients with respect to x, the gates and the three expert weights."""
    D, H, E, count, K, S, share = ROUTINGS[case]
    layer = _expert_layer(D, H, E, count, K)
    keys = jax.random.split(jax.random.PRNGKey(11), 7)
    shapes = {"wi_0": (count, D, H), "wi_1": (count, D, H),
              "wo": (count, H, D)}
    w = {n: jax.random.normal(k, s) / np.sqrt(s[-2])
         for (n, s), k in zip(shapes.items(), keys)}
    x = jax.random.normal(keys[3], (S, D))
    gates = jax.random.uniform(keys[4], (S, K))
    if share is None:
        experts = jnp.argsort(jax.random.uniform(keys[5], (S, E)), -1)[:, :K]
        local = jnp.where(experts < count, experts, count)
    else:
        local = jnp.where(jax.random.uniform(keys[5], (S, K)) < share,
                          jax.random.randint(keys[6], (S, K), 0, count),
                          count)
    probe = jnp.cos(jnp.arange(S * D, dtype=jnp.float32)).reshape(S, D)

    def objective(fn):
        def f(w, x, gates):
            y, sizes = fn(w, x, local, gates)
            return jnp.sum(y * probe), (y, sizes)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, (y, sizes)), grads = objective(layer._sequence)(w, x, gates)
    (_, (y0, sizes0)), grads0 = objective(
        lambda *a: _whole_sort_sequence(layer, *a))(w, x, gates)
    chunk, total = layer._chunk_rows(S), int(np.sum(sizes0))
    runs = -(-total // chunk)
    if case == "none_held":
        assert total == 0
    elif case == "all_held":
        assert total == S * K and runs == S * K // chunk > 1
    elif case == "held_not_a_chunk_multiple":
        assert total % chunk and 1 < runs < S * K // chunk
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(sizes0))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=TOL,
                               atol=TOL)
    for name, got, want in zip(("wi_0", "wi_1", "wo", "x", "gates"),
                               [*(grads[0][k] for k in shapes), *grads[1:]],
                               [*(grads0[0][k] for k in shapes),
                                *grads0[1:]]):
        scale = float(np.max(np.abs(np.asarray(want)))) or 1.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=TOL, atol=TOL * scale, err_msg=name)


def test_held_chunk_share_counts_the_chunks_run():
    """``held_chunk_share`` reads ceil(held / C) / (S*K / C), averaged over
    the sequences, and ``held_assignments`` is the whole sort's count."""
    B, S, D = 3, 1024, 16
    layer = _expert_layer(D, 32, 8, 2, 2)
    keys = jax.random.split(jax.random.PRNGKey(12), 5)
    state = {"router": {"weight": jax.random.normal(keys[0], (D, 8)),
                        "bias": jnp.zeros((8,))},
             "wi_0": jax.random.normal(keys[1], (2, D, 32)),
             "wi_1": jax.random.normal(keys[2], (2, D, 32)),
             "wo": jax.random.normal(keys[3], (2, 32, D))}
    x = jax.random.normal(keys[4], (B, S, D))
    _, col = functional(layer, state=state, inputs=(x,))
    (experts, _), _ = functional(layer.router, state=state["router"],
                                 inputs=(x,))
    local = jnp.where(experts < 2, experts, 2)
    held = [int(np.sum(np.asarray(local[b]) < 2)) for b in range(B)]
    chunk, slots = layer._chunk_rows(S), S * 2 // layer._chunk_rows(S)
    want = np.mean([-(-h // chunk) for h in held]) / slots
    assert 0 < want < 1 and any(h % chunk for h in held)
    np.testing.assert_allclose(float(col.summaries["held_chunk_share"]),
                               want, rtol=1e-6)
    sizes0 = [_whole_sort_sequence(layer, state, x[b], local[b],
                                   jnp.ones((S, 2)))[1] for b in range(B)]
    assert float(col.summaries["held_assignments"]) == sum(
        int(np.sum(s)) for s in sizes0) == sum(held)


def test_chunk_rule_and_one_grouped_product_per_weight(monkeypatch):
    """At the cell's shapes a chunk is a multiple of the grouped products'
    row tile that divides S*K, and a sequence still makes one grouped
    product per expert weight."""
    from repro.kernels.gmm import tiling_for

    layer = _expert_layer(2048, 1408, 64, 8, 6)
    S, K = 8192, 6
    chunk = layer._chunk_rows(S)
    tile = tiling_for(S * K, 2048, 1408)[0]
    assert chunk % tile == 0 and (S * K) % chunk == 0
    assert 3 <= S * K * 8 // 64 // chunk <= 12
    calls = []
    real = kernel_ops.grouped_matmul
    monkeypatch.setattr(kernel_ops, "grouped_matmul",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    w = {"wi_0": jnp.zeros((8, 2048, 1408)), "wi_1": jnp.zeros((8, 2048, 1408)),
         "wo": jnp.zeros((8, 1408, 2048))}
    jax.eval_shape(layer._sequence, w, jnp.zeros((S, 2048), jnp.bfloat16),
                   jnp.zeros((S, K), jnp.int32), jnp.zeros((S, K)))
    assert len(calls) == 3
