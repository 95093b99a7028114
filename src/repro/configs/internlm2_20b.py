"""internlm2-20b [dense]: a plain GQA decoder with a SwiGLU MLP.

Published widths [hf:internlm/internlm2-20b; arXiv:2403.17297]: 48L
d_model=6144 48H (GQA kv=8) head_dim=128 d_ff=16384 vocab=92544,
rms_norm_eps 1e-5, rope_theta 1e6, untied output head, no q/k norm.  The
published ``rope_scaling`` (dynamic NTK) changes nothing below
``max_position_embeddings`` (32768) and is not implemented.  The
checkpoint packs q, k and v into one ``wqkv`` per layer; the program keeps
them as three projections (``bench/configs/internlm2-20b.json``'s
``layout`` maps one onto the other).
"""
from .base import LayerSpec, ModelConfig

_L = LayerSpec(mixer="gqa", ffn="swiglu")

CONFIG = ModelConfig(
    name="internlm2-20b", family="dense",
    n_layers=48, d_model=6144, n_heads=48, n_kv_heads=8, head_dim=128,
    d_ff=16384, vocab=92544,
    pattern=(_L,),
    norm_eps=1e-5, rope_theta=1e6, tie_embeddings=False,
    sub_quadratic=False,
)


def reduced() -> ModelConfig:
    return ModelConfig(
        name="internlm2-smoke", family="dense",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=256,
        pattern=(_L,), norm_eps=1e-5, rope_theta=1e6, tie_embeddings=False,
    )
