"""Plain reference for the Moonlight-16B-A3B configurations: the
DeepSeek-V3 block (arXiv:2412.19437) at 16B-A3B (arXiv:2502.16982).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``; it
imports nothing of the program under test, and borrows the qwen2
reference's shared pieces (seeding, float8 control, RMSNorm, AdamW):

* ``make_weights`` / ``weights_fn``: the benchmark's weights from the seed,
  in a plain layout whose leaves have the program's shapes;
  ``to_program_tree`` renames them into the program's tree (no arithmetic);
* ``hidden`` / ``loss``: embedding; layer 0 (latent attention, dense
  SwiGLU); the expert layers (latent attention; sigmoid routing with the
  selection bias; the held experts, each computed densely over every token
  and weighed by its gate where chosen; the shared experts); final RMSNorm;
  untied head; cross entropy plus the sequence-wise balance loss. RoPE
  rotates adjacent pairs and returns them de-interleaved (DeepSeek's
  layout); k's RoPE slice is one head, shared by all;
* the share: the configuration holds experts ``first_held_expert`` ..
  ``+ n_routed_experts`` of ``router_experts``; the router routes over all
  of them and only the held ones are computed, as in the program;
* the bias update: the bias takes no gradient, so its slot in the gradient
  carries the step's load signal (load minus mean load, from a term of the
  loss whose value is exactly 0), and ``adamw_step`` moves it by
  ``-gamma * sign`` of that: b += gamma * sign(mean load - load);
* ``apply_sizes`` (the configuration file's sizes on the program's model
  config) and ``flops_per_token`` (the cell's model FLOPs).
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def _load_qwen2():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chipbench_configs_qwen2_shared", os.path.join(HERE, "qwen2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_Q = _load_qwen2()
seed_key, adamw_init, _mm, _rms = _Q.seed_key, _Q.adamw_init, _Q._mm, _Q._rms

# Assumed (the config file's ``assumed``): the KV latent norm's eps, the
# balance loss's alpha and the bias update's gamma.
KV_NORM_EPS = 1e-6
BALANCE_ALPHA = 1e-4
BIAS_GAMMA = 1e-3

# Plain leaves. Matrix names start with "w": the qwen2 reference's AdamW
# decays exactly those (not the embedding, the norm scales or the bias), as
# the program does. Leaves ending in "0" are the dense layer's.
ATTN = ("attn_norm", "wq", "wkva", "kv_norm", "wkvb", "wo", "ffn_norm")
DENSE = tuple(f"{n}0" for n in ATTN + ("w_gate", "w_up", "w_down"))
MOE = ATTN + ("w_router", "router_bias", "we_gate", "we_up", "we_down",
              "ws_gate", "ws_up", "ws_down")
BIAS = "router_bias"


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], latent=cfg["kv_lora_rank"],
        f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts=cfg["router_experts"], held=cfg["n_routed_experts"],
        first=cfg["first_held_expert"], k=cfg["num_experts_per_tok"],
        dense=cfg["first_k_dense_replace"],
        moe=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        v=cfg["vocab_size"])


def leaf_shapes(cfg) -> Dict[str, tuple]:
    s = sizes(cfg)
    d, h, qk = s["d"], s["h"], s["nope"] + s["rope"]

    def attn(n):
        return {"attn_norm": (n, d), "ffn_norm": (n, d),
                "wq": (n, d, h * qk), "wkva": (n, d, s["latent"] + s["rope"]),
                "kv_norm": (n, s["latent"]),
                "wkvb": (n, s["latent"], h * (s["nope"] + s["dv"])),
                "wo": (n, h * s["dv"], d)}

    L, E = s["moe"], s["held"]
    out = {"embed": (s["v"], d), "w_head": (d, s["v"]), "final_norm": (d,)}
    dense = attn(s["dense"])
    dense.update(w_gate=(s["dense"], d, s["f"]), w_up=(s["dense"], d, s["f"]),
                 w_down=(s["dense"], s["f"], d))
    out.update({f"{k}0": v for k, v in dense.items()})
    out.update(attn(L))
    out.update(w_router=(L, d, s["experts"]), router_bias=(L, s["experts"]),
               we_gate=(L, E, d, s["fe"]), we_up=(L, E, d, s["fe"]),
               we_down=(L, E, s["fe"], d), ws_gate=(L, d, s["fs"]),
               ws_up=(L, d, s["fs"]), ws_down=(L, s["fs"], d))
    return out


def weights_fn(cfg, dtype):
    """``key -> weights``: norm scales 1 + N(0, 0.1), the router bias
    N(0, 1e-3) (a few update steps' worth), the embedding N(0, 0.02),
    every matrix N(0, 1/fan_in)."""
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)

    def build(key):
        out = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            shape = shapes[name]
            x = jax.random.normal(k, shape, jnp.float32)
            if name == "embed":
                x = x * 0.02
            elif name == BIAS:
                x = x * BIAS_GAMMA
            elif "norm" in name:
                x = 1.0 + 0.1 * x
            else:
                x = x / math.sqrt(shape[-2])
            out[name] = x.astype(dtype)
        return out

    return build


def make_weights(cfg, seed: int, dtype, shardings=None) -> Dict[str, jax.Array]:
    return jax.jit(weights_fn(cfg, dtype), out_shardings=shardings)(
        seed_key(seed))


# Plain leaf -> path in the program's tree (CausalLM / Decoder /
# StackedTransformer of Repeat(dense layer) and Repeat(expert layer)). The
# program's gated FFNs: up_proj0 / wi_0 is the linear branch, up_proj1 /
# wi_1 the SiLU branch.
_ATTN_PATHS = {
    "attn_norm": ("attn_norm", "scale"), "ffn_norm": ("ffn_norm", "scale"),
    "wq": ("self_attention", "q_proj", "weight"),
    "wkva": ("self_attention", "kv_a_proj", "weight"),
    "kv_norm": ("self_attention", "kv_norm", "scale"),
    "wkvb": ("self_attention", "kv_b_proj", "weight"),
    "wo": ("self_attention", "o_proj", "weight"),
}
_DENSE0 = ("decoder", "stack", "layer0", "layer")
_MOE = ("decoder", "stack", "layer1", "layer")
_FF = _MOE + ("feed_forward",)
PROGRAM_PATHS = {
    "embed": ("decoder", "emb", "weight"),
    "w_head": ("decoder", "lm_head", "weight"),
    "final_norm": ("decoder", "final_norm", "scale"),
    **{f"{k}0": _DENSE0 + p for k, p in _ATTN_PATHS.items()},
    "w_up0": _DENSE0 + ("feed_forward", "up_proj0", "weight"),
    "w_gate0": _DENSE0 + ("feed_forward", "up_proj1", "weight"),
    "w_down0": _DENSE0 + ("feed_forward", "down_proj", "weight"),
    **{k: _MOE + p for k, p in _ATTN_PATHS.items()},
    "w_router": _FF + ("router", "weight"),
    BIAS: _FF + ("router", "bias"),
    "we_up": _FF + ("wi_0",), "we_gate": _FF + ("wi_1",),
    "we_down": _FF + ("wo",),
    "ws_up": _FF + ("shared_experts", "up_proj0", "weight"),
    "ws_gate": _FF + ("shared_experts", "up_proj1", "weight"),
    "ws_down": _FF + ("shared_experts", "down_proj", "weight"),
}


def to_program_tree(plain: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for name, path in PROGRAM_PATHS.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = plain[name]
    return tree


def from_program_tree(tree: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for name, path in PROGRAM_PATHS.items():
        node = tree
        for key in path:
            node = node[key]
        out[name] = node
    return out


# ------------------------------------------------------------------ model


def _rope(x, positions, theta):
    """(B, S, H, D): rotates the pairs (x[2i], x[2i+1]) by
    position * theta^(-2i/D) and returns them de-interleaved."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, d/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(mm, q, k, v, q_block):
    """Causal attention, queries in blocks of ``q_block`` rows (each block
    rematerialised) so that the score matrix never exists whole."""
    B, S, H, D = q.shape
    nb = S // q_block
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i, qi):
        s = mm("bqhd,bthd->bhqt", qi, k) / math.sqrt(D)
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return mm("bhqt,bthd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(nb),
                       jnp.moveaxis(q.reshape(B, nb, q_block, H, D), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


def _mla(cfg, mm, lw, x, q_block):
    s = sizes(cfg)
    B, S, _ = x.shape
    h, nope, rope = s["h"], s["nope"], s["rope"]
    pos = jnp.arange(S)
    theta = cfg["rope_theta"]
    q = mm("bsd,de->bse", x, lw["wq"]).reshape(B, S, h, nope + rope)
    kva = mm("bsd,de->bse", x, lw["wkva"])
    c_kv = _rms(kva[..., :s["latent"]], lw["kv_norm"], KV_NORM_EPS)
    k_pe = _rope(kva[..., s["latent"]:].reshape(B, S, 1, rope), pos, theta)
    kv = mm("bsc,ce->bse", c_kv, lw["wkvb"]).reshape(B, S, h, nope + s["dv"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (B, S, h, rope))], -1)
    a = _attention(mm, q, k, kv[..., nope:], q_block).reshape(B, S, -1)
    return mm("bse,ed->bsd", a, lw["wo"])


def _swiglu(mm, x, w_gate, w_up, w_down):
    g = mm("sd,df->sf", x, w_gate)
    u = mm("sd,df->sf", x, w_up)
    return mm("sf,fd->sd", jax.nn.silu(g) * u, w_down)


def _by_tokens(fn, *xs, block=2048):
    """``fn`` (tokens, ...) -> (tokens, ...) over blocks of ``block`` tokens
    of the (B, S, ...) arrays ``xs``, one block at a time and
    rematerialised, so that one block's FFN activations are live at once."""
    B, S = xs[0].shape[:2]
    block = min(block, S)
    blocks = [x.reshape(B * S // block, block, *x.shape[2:]) for x in xs]
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a), blocks)
    return out.reshape(B, S, *out.shape[2:])


def _experts(cfg, mm, lw, x):
    """(routed + shared output, balance loss, bias probe) of one expert
    layer over x (B, S, d)."""
    s = sizes(cfg)
    E, K = s["experts"], s["k"]
    B, S, _ = x.shape
    scores = jax.nn.sigmoid(mm("bsd,de->bse", x, lw["w_router"]))
    bias = lw[BIAS]
    _, top = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), K)
    chosen = jnp.take_along_axis(scores, top, axis=-1)
    gates = chosen / jnp.sum(chosen, -1, keepdims=True) * cfg[
        "routed_scaling_factor"]
    picked = jax.nn.one_hot(top, E, dtype=jnp.float32)  # (B, S, K, E)
    weight = jnp.einsum("bske,bsk->bse", picked, gates)  # 0 where unchosen
    counts = jnp.sum(picked, axis=(1, 2))  # (B, E)
    # Sequence-wise balance loss over all experts.
    f = counts * (E / (K * S))
    p = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=1)
    balance = BALANCE_ALPHA * jnp.mean(jnp.sum(f * p, -1))
    # Value exactly 0; gradient (load - mean load) in the bias's slot.
    load = jnp.sum(counts, axis=0)
    probe = jnp.sum((bias - jax.lax.stop_gradient(bias))
                    * jax.lax.stop_gradient(load - jnp.mean(load)))
    # The held experts, each over every token, weighed where chosen.
    held = jax.lax.dynamic_slice_in_dim(weight, s["first"], s["held"],
                                        axis=-1)  # (B, S, held)

    def tokens(x, w):
        @jax.checkpoint
        def one(y, xs):
            wg, wu, wd, we = xs
            return y + we[:, None] * _swiglu(mm, x, wg, wu, wd), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            lw["we_gate"], lw["we_up"], lw["we_down"], jnp.moveaxis(w, -1, 0)))
        return y + _swiglu(mm, x, lw["ws_gate"], lw["ws_up"], lw["ws_down"])

    return _by_tokens(tokens, x, held), balance + probe


def hidden(cfg, w, ids, *, mm="fp32", q_block=128):
    """(final-norm hidden states (B, S, d), the expert layers' balance loss
    (plus the bias probe, exactly 0))."""
    mmf = _mm(mm)
    eps = cfg["rms_norm_eps"]
    S = ids.shape[1]
    q_block = min(q_block, S)
    assert S % q_block == 0, (S, q_block)
    x = jnp.take(w["embed"], ids, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def dense(x, lw):
        x = x + _mla(cfg, mmf, lw, _rms(x, lw["attn_norm"], eps), q_block)
        h = _rms(x, lw["ffn_norm"], eps)
        return x + _by_tokens(lambda h: _swiglu(
            mmf, h, lw["w_gate"], lw["w_up"], lw["w_down"]), h), None

    @jax.checkpoint
    def expert(x, lw):
        x = x + _mla(cfg, mmf, lw, _rms(x, lw["attn_norm"], eps), q_block)
        y, aux = _experts(cfg, mmf, lw, _rms(x, lw["ffn_norm"], eps))
        return x + y, aux

    x, _ = jax.lax.scan(dense, x, {n[:-1]: w[n] for n in DENSE})
    x, aux = jax.lax.scan(expert, x, {n: w[n] for n in MOE})
    return _rms(x, w["final_norm"], eps), jnp.sum(aux)


def loss(cfg, w, ids, labels, *, mm="fp32", tok_block=512):
    """Mean next-token cross entropy over all (B, S) labels, plus the
    expert layers' balance loss: the training objective. The head runs in
    blocks of ``tok_block`` tokens so that (B, S, V) logits never exist."""
    h, aux = hidden(cfg, w, ids, mm=mm)
    B, S, d = h.shape
    tok_block = min(tok_block, S)
    n = S // tok_block
    hs = jnp.moveaxis(h.reshape(B, n, tok_block, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n, tok_block), 1, 0)
    mmf = _mm(mm)

    @jax.checkpoint
    def block(carry, xs):
        hb, lb = xs
        logits = mmf("...d,dv->...v", hb, w["w_head"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(logz - lab), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (hs, ls))
    return total / (B * S) + aux


# -------------------------------------------------------------- optimizer


def adamw_step(opt, params, state, grads):
    """AdamW (the qwen2 reference's) on every leaf but the router bias,
    which moves by ``-gamma * sign`` of its load signal; its moments stay 0,
    as the program's do for a leaf with no gradient."""
    rest = lambda t: {k: v for k, v in t.items() if k != BIAS}  # noqa: E731
    new, st = _Q.adamw_step(opt, rest(params), {
        "mu": rest(state["mu"]), "nu": rest(state["nu"]),
        "count": state["count"]}, rest(grads))
    new[BIAS] = params[BIAS] - BIAS_GAMMA * jnp.sign(grads[BIAS])
    st["mu"][BIAS] = state["mu"][BIAS]
    st["nu"][BIAS] = state["nu"][BIAS]
    return new, st


# ------------------------------------------------------ sizes and FLOPs


def apply_sizes(model_cfg, cfg: Dict[str, Any]):
    """Sets the configuration file's sizes on the program's model config
    (``CausalLM`` over a ``StackedTransformer`` of ``Repeat(dense layer)``
    and ``Repeat(expert layer)``)."""
    s = sizes(cfg)
    dec = model_cfg.decoder
    dec.set(vocab_size=s["v"], dim=s["d"])
    dec.final_norm.set(eps=cfg["rms_norm_eps"])
    dense, moe = dec.stack.layers
    dense.set(num_layers=s["dense"])
    moe.set(num_layers=s["moe"])
    for rep in (dense, moe):
        layer = rep.layer
        layer.set(input_dim=s["d"])
        layer.norm.set(eps=cfg["rms_norm_eps"])
        attn = layer.self_attention
        attn.set(num_heads=s["h"], qk_nope_head_dim=s["nope"],
                 qk_rope_head_dim=s["rope"], v_head_dim=s["dv"],
                 kv_lora_rank=s["latent"])
        attn.kv_norm.set(eps=KV_NORM_EPS)
        attn.rope.set(theta=float(cfg["rope_theta"]))
    dense.layer.feed_forward.set(hidden_dim=s["f"])
    ff = moe.layer.feed_forward
    ff.set(hidden_dim=s["fe"], num_experts=s["experts"], top_k=s["k"],
           held_experts=(s["first"], s["held"]))
    ff.router.set(routed_scale=cfg["routed_scaling_factor"],
                  balance_weight=BALANCE_ALPHA, bias_rate=BIAS_GAMMA)
    ff.shared.set(hidden_dim=s["fs"])
    return model_cfg


def matmul_params(cfg) -> float:
    """Weights that enter a matrix product, per token: projections, the
    dense FFN, the router, the shared experts, the routed experts at
    top_k x held / router_experts assignments a token (what this chip's
    experts compute), and the head; not the embedding lookup."""
    s = sizes(cfg)
    d, h = s["d"], s["h"]
    attn = (d * h * (s["nope"] + s["rope"]) + d * (s["latent"] + s["rope"])
            + s["latent"] * h * (s["nope"] + s["dv"]) + h * s["dv"] * d)
    routed = s["k"] * s["held"] / s["experts"] * 3 * d * s["fe"]
    moe = attn + d * s["experts"] + 3 * d * s["fs"] + routed
    return s["dense"] * (attn + 3 * d * s["f"]) + s["moe"] * moe + d * s["v"]


def attention_flops_per_token(cfg, seq: int) -> float:
    """Causal attention forward per token of a ``seq``-token row: QK^T
    (q/k head dim) and PV (v head dim) over (seq + 1) / 2 keys."""
    s = sizes(cfg)
    layers = s["dense"] + s["moe"]
    return layers * 2 * s["h"] * (s["nope"] + s["rope"] + s["dv"]) * (
        seq + 1) / 2


def flops_per_token(cfg, seq: int) -> float:
    """Training FLOPs per token: three times the forward pass (no
    recomputation)."""
    return 3 * (2 * matmul_params(cfg) + attention_flops_per_token(cfg, seq))
