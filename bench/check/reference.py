"""Plain reference of the two published decoders the benchmark serves.

Qwen3-MoE and InternLM2 share one set of layer equations: token embedding;
per layer, RMSNorm -> grouped-query attention with rotate-half RoPE (Qwen3
adds a per-head RMSNorm on q and k before RoPE) -> residual, RMSNorm -> FFN
-> residual; final RMSNorm and an untied output head.  The FFN is a SwiGLU
MLP (InternLM2) or a softmax router choosing the top-k of E SwiGLU experts
with the chosen gates renormalised to sum to 1 (Qwen3, ``norm_topk_prob``).

Everything here is float32 ``jax.numpy`` at HIGHEST matmul precision, one
sequence at a time, with no cache, no kernel and no batching: a causal
forward pass over a prompt and the tokens served after it.  It imports
nothing of the program.  It reads the benchmark-made weights (``bench/
weights.py``) in the program's storage layout, which ``LAYOUT`` spells out;
RMSNorm gains are stored as offsets from 1.

``control=True`` is the same reference one precision step down: every
matmul operand (weights per output column, activations per row) and the
attention K/V snapped to float8 e4m3 with an absmax scale.  It is the
benchmark's control: the comparison has to reject it.

Memory: attention runs in query blocks and the experts in blocks, layer by
layer with the layer index traced, so that a layer's float32 temporaries
fit beside the bf16 weights on one chip.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HI = lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
#: query rows per attention block, experts per expert block, head columns
#: split into this many blocks, sequence length and scored positions
#: rounded up to these (fewer distinct programs to compile)
Q_BLOCK, E_BLOCK, HEAD_SPLIT, S_BUCKET, P_BUCKET = 256, 16, 4, 1024, 256

#: where each published tensor sits in the weights (stacked layers on
#: axis 0 of every ``pattern`` leaf; projections stored [in, out])
LAYOUT = {
    "model.embed_tokens.weight": "embed [vocab_pad, d]",
    "model.norm.weight": "1 + norm_f.g",
    "lm_head.weight (transposed)": "lm_head [d, vocab_pad]",
    "layers.i.input_layernorm.weight": "1 + pattern[0].norm1.g[i]",
    "layers.i.self_attn.{q,k,v,o}_proj.weight (transposed)":
        "pattern[0].attn.{wq,wk,wv,wo}[i]",
    "layers.i.self_attn.{q,k}_norm.weight": "1 + pattern[0].attn.{q,k}_norm[i]",
    "layers.i.post_attention_layernorm.weight": "1 + pattern[0].norm2.g[i]",
    "layers.i.mlp.{gate,up,down}_proj.weight (transposed)":
        "pattern[0].mlp.{gate,up,down}[i]",
    "layers.i.mlp.gate.weight (router, transposed)": "pattern[0].mlp.router[i]",
    "layers.i.mlp.experts.e.{gate,up,down}_proj.weight (transposed)":
        "pattern[0].mlp.{w_gate,w_up,w_down}[i, e]",
}


@dataclasses.dataclass(frozen=True)
class Spec:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    eps: float
    theta: float
    qk_norm: bool
    experts: int
    top_k: int
    norm_topk: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        return cls(
            layers=int(cfg["num_hidden_layers"]), d=int(cfg["hidden_size"]),
            heads=int(cfg["num_attention_heads"]),
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or cfg["hidden_size"]
                         // cfg["num_attention_heads"]),
            vocab=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
            theta=float(cfg["rope_theta"]),
            qk_norm=cfg["model_type"] in ("qwen3", "qwen3_moe"),
            experts=int(cfg.get("num_experts", 0) or 0),
            top_k=int(cfg.get("num_experts_per_tok", 0) or 0),
            norm_topk=bool(cfg.get("norm_topk_prob", False)))


def _q8(x, axis):
    """Snap to float8 e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


def _mm(eq, x, w, control):
    """x (activations, contracted on its last axis) times w (weights,
    contracted on its second-to-last axis), in float32."""
    x, w = x.astype(F32), w.astype(F32)
    if control:
        x, w = _q8(x, -1), _q8(w, -2)
    return jnp.einsum(eq, x, w, precision=HI)


def _rms(x, g, eps):
    x = x.astype(F32)
    return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + g.astype(F32)))


def _rope(x, theta):
    """Rotate-half RoPE over x [S, n, Dh] at positions 0..S-1."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, spec):
    """Causal GQA: q [S, H, Dh], k/v [S, Hkv, Dh] -> [S, H * Dh]; query
    head h reads kv head h // (H / Hkv)."""
    s_len = q.shape[0]
    g = spec.heads // spec.kv_heads
    nb = s_len // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, spec.kv_heads, g, spec.head_dim)
    keys = jnp.arange(s_len)

    def block(args):
        j, qj = args
        sc = jnp.einsum("qhgd,khd->hgqk", qj, k, precision=HI)
        sc = sc * spec.head_dim ** -0.5
        rows = j * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(rows[:, None] >= keys[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    o = lax.map(block, (jnp.arange(nb), qb))
    return o.reshape(s_len, spec.heads * spec.head_dim)


def _moe(h, mlp, i, spec, control):
    """Softmax router, top-k, renormalised gates; every expert computed
    for every row in blocks and weighted by its gate (0 when unchosen)."""
    router = lax.dynamic_index_in_dim(mlp["router"], i, 0, keepdims=False)
    probs = jax.nn.softmax(jnp.einsum("sd,de->se", h, router.astype(F32),
                                      precision=HI), -1)
    top_v, top_i = lax.top_k(probs, spec.top_k)
    if spec.norm_topk:
        top_v = top_v / jnp.sum(top_v, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None],
                                    top_i].set(top_v)
    y = jnp.zeros_like(h)
    eb = min(E_BLOCK, spec.experts)
    for e0 in range(0, spec.experts, eb):
        def take(name, e0=e0):
            w = mlp[name]
            return lax.dynamic_slice(
                w, (i, e0, 0, 0), (1, eb) + w.shape[2:])[0].astype(F32)
        a = _mm("sd,edf->esf", h, take("w_gate"), control)
        u = _mm("sd,edf->esf", h, take("w_up"), control)
        z = jax.nn.silu(a) * u
        wd = take("w_down")
        if control:
            z, wd = _q8(z, -1), _q8(wd, -2)
        z = z * gate[:, e0:e0 + eb].T[:, :, None]
        y = y + jnp.einsum("esf,efd->sd", z, wd, precision=HI)
    return y


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _layer(pattern, i, x, spec, control):
    at = lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    p = pattern[0]
    s_len = x.shape[0]
    h = _rms(x, at(p["norm1"]["g"]), spec.eps)
    q = _mm("sd,de->se", h, at(p["attn"]["wq"]), control).reshape(
        s_len, spec.heads, spec.head_dim)
    k = _mm("sd,de->se", h, at(p["attn"]["wk"]), control).reshape(
        s_len, spec.kv_heads, spec.head_dim)
    v = _mm("sd,de->se", h, at(p["attn"]["wv"]), control).reshape(
        s_len, spec.kv_heads, spec.head_dim)
    if spec.qk_norm:
        q = _rms(q, at(p["attn"]["q_norm"]), spec.eps)
        k = _rms(k, at(p["attn"]["k_norm"]), spec.eps)
    q, k = _rope(q, spec.theta), _rope(k, spec.theta)
    if control:
        k, v = _q8(k, -1), _q8(v, -1)
    x = x + _mm("se,ed->sd", _attention(q, k, v, spec), at(p["attn"]["wo"]),
                control)
    h = _rms(x, at(p["norm2"]["g"]), spec.eps)
    if spec.experts:
        return x + _moe(h, p["mlp"], i, spec, control)
    g = _mm("sd,df->sf", h, at(p["mlp"]["gate"]), control)
    u = _mm("sd,df->sf", h, at(p["mlp"]["up"]), control)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, at(p["mlp"]["down"]),
                   control)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def hidden(params, spec: Spec, tokens, control: bool = False):
    """Final-layer residual stream [S_pad, d] of ``tokens`` (right-padded
    to a multiple of ``S_BUCKET``; padding sits after every real position,
    so causal attention keeps it out of them)."""
    n = len(tokens)
    s_pad = -(-n // S_BUCKET) * S_BUCKET
    t = np.zeros((s_pad,), np.int32)
    t[:n] = tokens
    x = _embed(params["embed"], jnp.asarray(t))
    for i in range(spec.layers):
        x = _layer(params["pattern"], jnp.int32(i), x, spec, control)
    return x


@functools.partial(jax.jit, static_argnames=("spec",))
def _head(params, x, xc, pos, served, spec):
    """Per scored position: the reference's best logit, its logit of the
    served token, and (with ``xc``, the control's residual stream) its
    logit of the token the control puts first."""
    nf = params["norm_f"]["g"]
    h = _rms(x[pos], nf, spec.eps)
    hc = None if xc is None else _rms(xc[pos], nf, spec.eps)
    w = params["lm_head"]
    width = w.shape[1] // HEAD_SPLIT
    n = pos.shape[0]
    best = jnp.full((n,), -jnp.inf, F32)
    got = jnp.zeros((n,), F32)
    c_best = jnp.full((n,), -jnp.inf, F32)
    c_ref = jnp.zeros((n,), F32)
    for b in range(HEAD_SPLIT):
        wb = lax.dynamic_slice_in_dim(w, b * width, width, 1)
        cols = b * width + jnp.arange(width)
        live = cols < spec.vocab
        lg = jnp.where(live, _mm("nd,dv->nv", h, wb, False), -jnp.inf)
        best = jnp.maximum(best, jnp.max(lg, -1))
        inb = (served >= b * width) & (served < (b + 1) * width)
        got = jnp.where(inb, jnp.take_along_axis(
            lg, jnp.clip(served - b * width, 0, width - 1)[:, None], 1)[:, 0],
            got)
        if hc is not None:
            lc = jnp.where(live, _mm("nd,dv->nv", hc, wb, True), -jnp.inf)
            mc, ac = jnp.max(lc, -1), jnp.argmax(lc, -1)
            at_c = jnp.take_along_axis(lg, ac[:, None], 1)[:, 0]
            c_ref = jnp.where(mc > c_best, at_c, c_ref)
            c_best = jnp.maximum(c_best, mc)
    return best, got, c_ref


def score(params, spec: Spec, prompt, served, control: bool = False) -> dict:
    """Teacher-force ``prompt`` + ``served`` through the reference.  For
    every served token: ``gap``, the reference's best logit minus its logit
    of the served token (0 when it is the argmax).  With ``control``:
    ``control_gap``, the same gap for the token the float8 control puts
    first at that position."""
    served = list(served)
    n = len(served)
    tokens = list(prompt) + served[:-1]
    n_pad = -(-n // P_BUCKET) * P_BUCKET        # one head program per bucket
    pos = np.full((n_pad,), len(prompt) - 1, np.int32)
    pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    want = np.zeros((n_pad,), np.int32)
    want[:n] = served
    x = hidden(params, spec, tokens)
    xc = hidden(params, spec, tokens, control=True) if control else None
    best, got, c_ref = (a[:n] for a in jax.device_get(_head(
        params, x, xc, jnp.asarray(pos), jnp.asarray(want), spec)))
    out = {"gap": best - got}
    if control:
        out["control_gap"] = best - c_ref
    return out
