"""Plain reference for the qwen2 configurations (arXiv:2407.10671).

Everything here is straightforward ``jax.numpy`` and imports nothing of the
program under test:

* ``make_weights``: the benchmark's own weights, drawn from the seed in one
  jitted call, in a plain layout (per-layer leaves stacked on a leading
  axis) and in the dtype they are trained or served in;
* ``to_program_tree``: a pure renaming of those leaves into the parameter
  tree the program's ``CausalLM`` expects (no arithmetic);
* ``hidden`` / ``head`` / ``loss``: the model in float32 at
  ``Precision.HIGHEST``: RMSNorm (eps from the config), RoPE with half
  rotation, grouped-query causal attention with q/k/v biases, SwiGLU, tied
  embeddings;
* ``adamw_step``: AdamW as the configuration states it (global-norm clip,
  bias-corrected moments, decoupled weight decay on matrices only, linear
  warm-up then cosine);
* the control: the same functions with every matrix product computed from
  float8 operands with per-tensor scales (e4m3 forward, e5m2 gradients, as
  fp8 training runs them), the precision below the configuration's
  bfloat16 (``mm="fp8"``).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# The per-layer leaves of the plain layout, stacked over layers.
LAYER_LEAVES = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                "ffn_norm", "w_gate", "w_up", "w_down")


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    return dict(d=d, hq=hq, hkv=hkv, hd=hd, f=cfg["intermediate_size"],
                v=cfg["vocab_size"], layers=cfg["num_hidden_layers"])


def seed32(seed: int) -> int:
    """A 32-bit key seed that keeps every bit of an arbitrary integer seed
    (``PRNGKey`` alone would drop the bits above 32)."""
    return int(np.random.SeedSequence(abs(int(seed))).generate_state(1)[0])


def leaf_shapes(cfg) -> Dict[str, tuple]:
    s = sizes(cfg)
    d, hq, hkv, hd, f, L = s["d"], s["hq"], s["hkv"], s["hd"], s["f"], s["layers"]
    return {
        "embed": (s["v"], d), "final_norm": (d,),
        "attn_norm": (L, d), "ffn_norm": (L, d),
        "wq": (L, d, hq * hd), "bq": (L, hq * hd),
        "wk": (L, d, hkv * hd), "bk": (L, hkv * hd),
        "wv": (L, d, hkv * hd), "bv": (L, hkv * hd),
        "wo": (L, hq * hd, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }


def _init_std(name: str, shape) -> float:
    if name == "embed":
        return 0.02
    if name.startswith("b"):
        return 0.1
    if name.endswith("norm"):
        return 0.1  # around 1, see make_weights
    return 1.0 / math.sqrt(shape[-2])


def weights_fn(cfg, dtype):
    """``key -> weights``: pure, so that one compiled program serves every
    seed. Norm scales are 1 + N(0, 0.1), biases N(0, 0.1), matrices
    N(0, 1/fan_in), the embedding N(0, 0.02)."""
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)

    def build(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            x = jax.random.normal(k, shapes[name], jnp.float32)
            x = x * _init_std(name, shapes[name])
            if name.endswith("norm"):
                x = x + 1.0
            out[name] = x.astype(dtype)
        return out

    return build


def seed_key(seed: int):
    return jax.random.PRNGKey(seed32(seed))


def make_weights(cfg, seed: int, dtype, shardings=None) -> Dict[str, jax.Array]:
    """The weights of one run, on the device, from ``seed``: one jitted
    call (``shardings``: optional plain-keyed output shardings)."""
    return jax.jit(weights_fn(cfg, dtype), out_shardings=shardings)(
        seed_key(seed))


# Plain leaf -> path in the program's parameter tree (CausalLM/Decoder/
# Repeat/TransformerLayer). up_proj0 is the linear branch and up_proj1 the
# SiLU branch of the program's ("linear", "nn.silu") gated FFN.
PROGRAM_PATHS = {
    "embed": ("decoder", "emb", "weight"),
    "final_norm": ("decoder", "final_norm", "scale"),
    "attn_norm": ("decoder", "stack", "layer", "attn_norm", "scale"),
    "ffn_norm": ("decoder", "stack", "layer", "ffn_norm", "scale"),
    "wq": ("decoder", "stack", "layer", "self_attention", "q_proj", "weight"),
    "bq": ("decoder", "stack", "layer", "self_attention", "q_proj", "bias"),
    "wk": ("decoder", "stack", "layer", "self_attention", "k_proj", "weight"),
    "bk": ("decoder", "stack", "layer", "self_attention", "k_proj", "bias"),
    "wv": ("decoder", "stack", "layer", "self_attention", "v_proj", "weight"),
    "bv": ("decoder", "stack", "layer", "self_attention", "v_proj", "bias"),
    "wo": ("decoder", "stack", "layer", "self_attention", "o_proj", "weight"),
    "w_up": ("decoder", "stack", "layer", "feed_forward", "up_proj0", "weight"),
    "w_gate": ("decoder", "stack", "layer", "feed_forward", "up_proj1",
               "weight"),
    "w_down": ("decoder", "stack", "layer", "feed_forward", "down_proj",
               "weight"),
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


def _round_fp8(x, dtype, top):
    """Per-tensor scaled rounding to a float8 ``dtype`` whose largest
    finite value is ``top``, back in float32."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, top / amax, 1.0)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _fp8(x):
    """A matrix-product operand as fp8 training computes it: e4m3 in the
    forward pass, and the gradient that flows back through it in e5m2,
    each with a per-tensor scale."""
    return _round_fp8(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_fp8(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(mode):
    def mm(spec, a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if mode == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """(B, S, H, D), half rotation (rotate_half convention)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, d/2)
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(mm, q, k, v, q_block):
    """Causal GQA attention, queries in blocks of ``q_block`` rows (each
    block rematerialised) so that the score matrix never exists whole."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    nb = S // q_block
    qb = q.reshape(B, nb, q_block, Hkv, G, D)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i, qi):
        s = mm("bqkgd,btkd->bkgqt", qi, k) / math.sqrt(D)
        qpos = i * q_block + jnp.arange(q_block)
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bkgqt,btkd->bqkgd", p, v)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(nb), jnp.moveaxis(qb, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, Hq, D)


def hidden(cfg, w, ids, *, mm="fp32", q_block=512):
    """Final-norm hidden states (B, S, d) of ``ids`` (B, S)."""
    s = sizes(cfg)
    mmf = _mm(mm)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    B, S = ids.shape
    q_block = min(q_block, S)
    assert S % q_block == 0, (S, q_block)
    pos = jnp.arange(S)
    x = jnp.take(w["embed"], ids, axis=0).astype(jnp.float32)
    layer_w = {n: w[n] for n in LAYER_LEAVES}

    @jax.checkpoint
    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], eps)
        q = (mmf("bsd,de->bse", h, lw["wq"]) + lw["bq"].astype(jnp.float32))
        k = (mmf("bsd,de->bse", h, lw["wk"]) + lw["bk"].astype(jnp.float32))
        v = (mmf("bsd,de->bse", h, lw["wv"]) + lw["bv"].astype(jnp.float32))
        q = _rope(q.reshape(B, S, s["hq"], s["hd"]), pos, theta)
        k = _rope(k.reshape(B, S, s["hkv"], s["hd"]), pos, theta)
        v = v.reshape(B, S, s["hkv"], s["hd"])
        a = _attention(mmf, q, k, v, q_block).reshape(B, S, -1)
        x = x + mmf("bse,ed->bsd", a, lw["wo"])
        h = _rms(x, lw["ffn_norm"], eps)
        g = mmf("bsd,df->bsf", h, lw["w_gate"])
        u = mmf("bsd,df->bsf", h, lw["w_up"])
        x = x + mmf("bsf,fd->bsd", jax.nn.silu(g) * u, lw["w_down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, layer_w)
    return _rms(x, w["final_norm"], eps)


def head(w, h, *, mm="fp32"):
    """Tied-embedding logits of hidden states (..., d)."""
    return _mm(mm)("...d,vd->...v", h, w["embed"])


def loss(cfg, w, ids, labels, *, mm="fp32", tok_block=512):
    """Mean next-token cross entropy over all (B, S) labels, the head in
    blocks of ``tok_block`` tokens so that (B, S, V) logits never exist."""
    h = hidden(cfg, w, ids, mm=mm)
    B, S, d = h.shape
    tok_block = min(tok_block, S)
    n = S // tok_block
    hs = jnp.moveaxis(h.reshape(B, n, tok_block, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, n, tok_block), 1, 0)

    @jax.checkpoint
    def block(carry, xs):
        hb, lb = xs
        logits = head(w, hb, mm=mm)
        logz = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(logz - lab), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (hs, ls))
    return total / (B * S)


# -------------------------------------------------------------- optimizer


def decays(name: str) -> bool:
    """Decoupled weight decay applies to the projection matrices only:
    not to the embedding, the biases or the norm scales."""
    return name.startswith("w")


def lr_at(opt, step):
    """Linear warm-up from 0 over ``warmup_steps``, then cosine to
    ``end_lr_ratio`` of the peak at ``total_steps``."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    step = jnp.asarray(step, jnp.float32)
    progress = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (opt["end_lr_ratio"] + (1 - opt["end_lr_ratio"]) * 0.5
                  * (1 + jnp.cos(jnp.pi * progress)))
    return jnp.where(step < warm, peak * step / max(warm, 1), cos)


def adamw_init(params):
    def zeros():
        return {k: jnp.zeros_like(v, jnp.float32) for k, v in params.items()}

    return {"mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.int32)}


def adamw_step(opt, params, state, grads):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    clip = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-9))
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    count = state["count"] + 1
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    lr = lr_at(opt, state["count"])
    mu, nu, new = {}, {}, {}
    for k, p in params.items():
        g = grads[k] * clip
        mu[k] = b1 * state["mu"][k] + (1 - b1) * g
        nu[k] = b2 * state["nu"][k] + (1 - b2) * jnp.square(g)
        u = (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + eps)
        if decays(k):
            u = u + opt["weight_decay"] * p
        new[k] = p + (-lr) * u
    return new, {"mu": mu, "nu": nu, "count": count}
