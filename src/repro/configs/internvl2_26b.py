"""internvl2-26b [vlm]: InternViT + InternLM2-20B backbone.

Assignment: 48L d_model=6144 48H (GQA kv=8) d_ff=16384 vocab=92553
[arXiv:2404.16821; hf].  The backbone's widths are ``internlm2_20b``'s;
the VLM keeps its own vocabulary.  The ViT frontend is a STUB per the
assignment: ``input_specs()`` provides precomputed patch embeddings that
occupy the first ``n_frontend_tokens`` positions of the sequence.
"""
import dataclasses

from . import internlm2_20b
from .base import ModelConfig

_L = internlm2_20b.CONFIG.pattern[0]

CONFIG = dataclasses.replace(
    internlm2_20b.CONFIG, name="internvl2-26b", family="vlm", vocab=92553,
    frontend="patch", n_frontend_tokens=256,
)


def reduced() -> ModelConfig:
    return ModelConfig(
        name="internvl2-26b-smoke", family="vlm",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=256,
        pattern=(_L,), tie_embeddings=False,
        frontend="patch", n_frontend_tokens=8,
    )
