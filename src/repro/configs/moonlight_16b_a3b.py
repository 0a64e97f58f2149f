"""moonlight-16b-a3b [moe] 27L d=2048, MLA 16H, 64 experts top-6 + 2 shared,
vocab=163840 (untied).

The DeepSeek-V3 block [arXiv:2412.19437] at 16B-A3B (Moonlight,
arXiv:2502.16982; config: huggingface.co/moonshotai/Moonlight-16B-A3B):

* latent attention without a query LoRA: 16 heads, q/k 128 + 64 RoPE wide
  (DeepSeek's pair layout, theta 50,000), v 128, a 512-wide KV latent with
  its own RMSNorm (eps 1e-6, the HF DeepseekV3 default);
* layer 0 dense (SwiGLU 11,264), layers 1-26 experts: 64 routed of width
  1,408, top-6 by sigmoid scores plus a selection bias (``noaux_tc``, one
  group), gates normalised and scaled by 2.446, and 2 shared experts (one
  SwiGLU of 2,816); the sequence-wise balance loss (alpha 1e-4) and the
  bias update (gamma 1e-3) are the DeepSeek-V3 paper's values;
* RMSNorm eps 1e-5, untied head, 8k context.

``make_share`` is one chip's share of training in an 8-chip expert-parallel
group: the chips split each expert layer's experts and the vocabulary, so
this chip holds experts 0-7 of 64 and a 20,480-row slice of the vocabulary;
the dense layer and 5 of the 26 expert layers stand for the depth (the other
layers would lie on further chips as pipeline stages).
"""

from repro.configs import common as c
from repro.layers import RMSNorm, StackedTransformer
from repro.layers.mla import LatentAttention
from repro.layers.moe import DroplessMoE

ARCH_ID = "moonlight-16b-a3b"
VOCAB = 163840
DIM = 2048
# Tokens per chunk of the cross-entropy: (batch, 1024, vocab) fp32 logits at
# a time, never (batch, 8192, vocab).
LOSS_CHUNK = 1024


def _model(*, moe_layers, d, heads, nope, rope, v_dim, latent, dense_ffn,
           expert_ffn, experts, top_k, shared, held, vocab, remat="full"):
    attn = LatentAttention.default_config().set(
        num_heads=heads, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=v_dim, kv_lora_rank=latent)
    attn.kv_norm.set(eps=1e-6)
    attn.rope.set(theta=50000.0)
    norm = RMSNorm.default_config().set(eps=1e-5)
    dense = c.layer_cfg(d, attn, c.ffn_cfg(dense_ffn), norm=norm)
    moe = DroplessMoE.default_config().set(
        hidden_dim=expert_ffn, num_experts=experts, top_k=top_k,
        held_experts=held, shared=c.ffn_cfg(shared * expert_ffn))
    moe.router.set(routed_scale=2.446, balance_weight=1e-4, bias_rate=1e-3)
    layer = c.layer_cfg(d, attn.clone(), moe, norm=norm.clone())
    stack = StackedTransformer.default_config().set(layers=[
        c.repeat_cfg(dense, 1, remat=remat),
        c.repeat_cfg(layer, moe_layers, remat=remat)])
    dec = c.decoder_cfg(vocab_size=vocab, dim=d, stack=stack,
                        tied_embeddings=False, final_norm=norm.clone())
    return c.lm_cfg(dec).set(loss_chunk_size=LOSS_CHUNK)


def _published(**over):
    sizes = dict(moe_layers=26, d=DIM, heads=16, nope=128, rope=64, v_dim=128,
                 latent=512, dense_ffn=11264, expert_ffn=1408, experts=64,
                 top_k=6, shared=2, held=None, vocab=VOCAB)
    return _model(**{**sizes, **over})


def make_model():
    return _published()


def make_share():
    """One chip of an 8-chip expert-parallel group: experts 0-7, 1/8 of the
    vocabulary, the dense layer and 5 expert layers."""
    return _published(moe_layers=5, held=(0, 8), vocab=VOCAB // 8)


def make_smoke():
    return _model(moe_layers=2, d=64, heads=4, nope=16, rope=16, v_dim=16,
                  latent=32, dense_ffn=128, expert_ffn=32, experts=8, top_k=2,
                  shared=1, held=None, vocab=128, remat=None)


SPEC = c.ArchSpec(
    arch_id=ARCH_ID, family="moe", citation="arXiv:2502.16982",
    make_model=make_model, make_smoke=make_smoke, make_share=make_share,
    vocab_size=VOCAB, model_dim=DIM,
    skip_shapes={
        "prefill_32k": "training-path latent attention only: no KV cache",
        "decode_32k": "training-path latent attention only: no KV cache",
        "long_500k": "training-path latent attention only: no KV cache",
    },
)
