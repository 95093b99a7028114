"""Operations and bytes the served work requires, from request lengths and
the configuration's published shapes.

Nothing here looks at how the program computes: not the kernels' block
grids, not the drop-free expert capacity (which makes the program compute
every expert for every token), not padding.  A token needs its top-k
experts, a causal query needs the keys before it, a prefill chunk reads
the live K/V before its end once.  A share of a peak computed from these
counts therefore reads low where the program wastes work, and can never
pass 100% for a timing that covers the work.

FLOPs count a multiply-add as 2.  Bytes are at the precision the
configuration serves (bf16 activations and KV, 2 bytes).
"""
from __future__ import annotations

import dataclasses

ACT_BYTES = 2


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    d_ff: int = 0               # dense SwiGLU width (0 for MoE layers)
    experts: int = 0            # routed experts (0 for dense layers)
    top_k: int = 0
    d_expert: int = 0
    kv_bytes: int = ACT_BYTES

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        """From a configuration file's published keys (Hugging Face names)."""
        moe = int(cfg.get("num_experts", 0) or 0)
        return cls(
            layers=int(cfg["num_hidden_layers"]), d=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or cfg["hidden_size"]
                         // cfg["num_attention_heads"]),
            vocab=int(cfg["vocab_size"]),
            d_ff=0 if moe else int(cfg["intermediate_size"]),
            experts=moe, top_k=int(cfg.get("num_experts_per_tok", 0) or 0),
            d_expert=int(cfg.get("moe_intermediate_size", 0) or 0))

    # -- per token ---------------------------------------------------------
    def attn_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * q + 2 * self.d * kv + q * self.d

    def ffn_active_params(self) -> int:
        """Weights one token multiplies by in a layer's FFN: its top-k
        experts and the router for a MoE layer, the whole MLP otherwise."""
        if self.experts:
            return (self.top_k * 3 * self.d * self.d_expert
                    + self.d * self.experts)
        return 3 * self.d * self.d_ff

    def linear_flops_per_token(self) -> float:
        """Every layer's projections and FFN for one token (no attention
        scores, no output head)."""
        return 2.0 * self.layers * (self.attn_params()
                                    + self.ffn_active_params())

    def head_flops(self) -> float:
        """The output head for one position."""
        return 2.0 * self.d * self.vocab

    def attn_pair_flops(self) -> float:
        """QK^T and PV for one (query, key) pair, over all layers."""
        return 4.0 * self.layers * self.heads * self.head_dim

    def kv_bytes_per_token(self) -> float:
        """K and V of one position, over all layers."""
        return 2.0 * self.layers * self.kv_heads * self.head_dim * self.kv_bytes


def causal_pairs(n: int) -> float:
    """(query, key) pairs of a causal prompt of ``n`` tokens."""
    return n * (n + 1) / 2.0


def prefill_flops(s: Shape, prompt: int) -> float:
    """A whole prompt: projections and FFN of every token, causal
    attention, and the head at the last position (the first token)."""
    return (prompt * s.linear_flops_per_token()
            + causal_pairs(prompt) * s.attn_pair_flops() + s.head_flops())


def flash_prefill_work(s: Shape, prompt: int, chunk: int) -> tuple:
    """(flops, bytes) of the prefill attention kernel over a prompt served
    in ``chunk``-token pieces: each piece reads its queries once, the live
    K/V before its end once, and writes its output once."""
    flops = causal_pairs(prompt) * s.attn_pair_flops()
    q_row = s.heads * s.head_dim * ACT_BYTES
    nbytes = 0.0
    for a in range(0, prompt, chunk):
        b = min(prompt, a + chunk)
        nbytes += (b - a) * 2 * q_row + b * s.kv_bytes_per_token() / s.layers
    return flops, nbytes * s.layers


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take, and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
