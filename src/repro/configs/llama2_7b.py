"""llama2-7b — the paper's own evaluation model (Tables 3-4): 32L d=4096
32H MHA d_ff=11008 vocab=32000 [arXiv:2307.09288].

Not part of the assigned pool.
"""

from repro.configs import common as c

ARCH_ID = "llama2-7b"


def _model(L, d, Hq, Hkv, hd, dff, vocab, remat="full"):
    attn = c.attention_cfg(num_heads=Hq, num_kv_heads=Hkv, head_dim=hd,
                           rope_theta=10000.0)
    layer = c.layer_cfg(d, attn, c.ffn_cfg(dff))
    dec = c.decoder_cfg(vocab_size=vocab, dim=d,
                        stack=c.repeat_cfg(layer, L, remat=remat),
                        tied_embeddings=False)
    return c.lm_cfg(dec)


def make_model():
    return _model(32, 4096, 32, 32, 128, 11008, 32000)


def make_smoke():
    return _model(2, 128, 4, 4, 32, 256, 128, remat=None)


SPEC = c.ArchSpec(
    arch_id=ARCH_ID, family="dense", citation="arXiv:2307.09288",
    make_model=make_model, make_smoke=make_smoke,
    vocab_size=32000, model_dim=4096,
    skip_shapes={"long_500k": "pure full-attention dense arch; no sub-quadratic variant configured"},
)
