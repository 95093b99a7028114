"""Unified model composition for all assigned architectures.

A model is: embedding (+ optional modality-frontend stub) -> a stack of
layers described by ``cfg.prefix + cfg.pattern * repeats + cfg.suffix``
(the pattern part runs under ``lax.scan`` with stacked weights, keeping HLO
size O(1) in depth) -> final norm -> (tied) unembedding with chunked
cross-entropy.

Three entry points per model (the shapes of the assignment):
  ``forward_train``  — [B, S] tokens -> scalar loss (train_4k)
  ``prefill``        — [B, S] tokens -> (last-token logits, caches)  (prefill_32k)
  ``decode_step``    — one token + caches -> (logits, caches)  (decode_32k/long_500k)

plus the continuous-batching steps driven by ``launch/engine.py``:
  ``prefill_chunk``  — one prompt chunk into existing paged caches
  ``decode_round``   — one decode round over every batch slot
  ``decode_burst``   — a ``while_loop`` of rounds, exiting on any finish
and ``generate``, prefill plus a ``lax.scan`` of decode steps in one
program (with repetition/presence penalties riding the carry —
``apply_penalties``): the plain reference the engine is checked against.

Transprecision: every matmul routes through core.ops under the active
PrecisionPolicy; caches store in ``policy.kv_fmt``; softmax/norm/router
stay f32 (FPnew's COMP group).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import EncoderConfig, LayerSpec, ModelConfig
from ..core import ops as tp
from ..core.policy import PrecisionPolicy, get_policy
from . import attention as attn
from . import moe as moe_mod
from . import paged
from . import ssm
from .layers import (batch_axes, bspec, dense_init, embed_init, gelu_mlp,
                     layernorm, mlp_params, param_dtype, residual_spec,
                     rmsnorm, shard, softcap, swiglu)

F32 = jnp.float32

#: embeddings/unembeddings are padded to a multiple of this so the vocab
#: dimension shards evenly over any production model axis (16) and stays
#: MXU-lane aligned (128) — standard practice (MaxText etc.); the pad tail
#: is masked to -inf in logits and never trained or sampled.
VOCAB_PAD = 256


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def sample_token(lg, key, *, temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None):
    """One sampling step: logits [B, V] -> token ids [B] (int32).

    ``temperature <= 0`` is greedy argmax — bit-identical to the
    pre-sampling decode path (``key`` is ignored, so XLA dead-code-
    eliminates the PRNG plumbing).  Otherwise: temperature scaling, then
    optional top-k truncation, then optional nucleus (top-p) truncation —
    the smallest prefix of the sorted distribution whose mass reaches
    ``top_p`` is kept (always >= 1 token) — then a categorical draw.
    Truncated logits go to a large negative (not -inf: the vocab pad tail
    is already masked at -1e30 and stays unsampleable)."""
    lg = lg.astype(F32)
    if temperature is None or temperature <= 0.0:
        return jnp.argmax(lg, -1).astype(jnp.int32)
    lg = lg / temperature
    if top_k is not None and top_k > 0:
        kth = jax.lax.top_k(lg, min(top_k, lg.shape[-1]))[0][..., -1:]
        lg = jnp.where(lg < kth, -1e30, lg)
    if top_p is not None and top_p < 1.0:
        srt = jnp.sort(lg, axis=-1)[..., ::-1]
        prob = jax.nn.softmax(srt, axis=-1)
        exclusive_mass = jnp.cumsum(prob, axis=-1) - prob
        keep = exclusive_mass < top_p           # first token always kept
        kth = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
        lg = jnp.where(lg < kth, -1e30, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)


def apply_penalties(lg, counts, *, repetition_penalty: Optional[float] = None,
                    presence_penalty: Optional[float] = None):
    """Repetition/presence penalties on logits [B, V] from per-row token
    counts [B, V] (prompt + everything emitted so far).

    ``repetition_penalty`` (HF semantics, > 1 discourages): seen tokens'
    logits are divided by the penalty when positive, multiplied when
    negative.  ``presence_penalty`` (OpenAI semantics, > 0 discourages): a
    flat subtraction for every seen token.  Both key off *presence*
    (count > 0), are applied to the raw logits BEFORE temperature/top-k/
    top-p, and leave unseen tokens untouched — ``None``/neutral knobs are
    static, so the default graph carries no count state at all."""
    lg = lg.astype(F32)
    seen = counts > 0
    if repetition_penalty is not None and repetition_penalty != 1.0:
        rp = jnp.asarray(repetition_penalty, F32)
        lg = jnp.where(seen, jnp.where(lg > 0, lg / rp, lg * rp), lg)
    if presence_penalty is not None and presence_penalty != 0.0:
        lg = lg - jnp.asarray(presence_penalty, F32) * seen.astype(F32)
    return lg


def token_counts(tokens, vocab: int, prompt_lens=None):
    """Per-row token histogram [B, vocab] int32 of a (right-padded) prompt
    [B, S] — the count state penalties start from.  ``prompt_lens`` masks
    each row's pad tail out of the histogram (pad slots are not 'seen')."""
    b, s = tokens.shape
    live = jnp.ones((b, s), jnp.int32)
    if prompt_lens is not None:
        live = (jnp.arange(s)[None, :]
                < jnp.reshape(jnp.asarray(prompt_lens, jnp.int32),
                              (-1, 1))).astype(jnp.int32)
    cnt = jnp.zeros((b, vocab), jnp.int32)
    return cnt.at[jnp.arange(b)[:, None], tokens].add(live)


def _bump_counts(cnt, tok):
    """counts [B, V] += 1 at each row's emitted token [B, 1]."""
    b = cnt.shape[0]
    return cnt.at[jnp.arange(b), tok[:, 0]].add(1)


def sanitize_logits(lg):
    """Non-finite logits guard: NaN/Inf entries go to the same large
    negative the vocab pad tail uses (unsampleable), and each poisoned row
    is flagged.  Returns ``(clean [..., V], bad [...])`` — ``bad`` is True
    where ANY entry of the row was non-finite.  On finite logits the mask
    is a no-op, so guarded and unguarded sampling stay bit-identical; on a
    fully-poisoned row every logit collapses to the floor and argmax
    deterministically picks token 0 — callers decide whether that flag is
    fatal (fail fast) or counted (fault-harness mask-and-flag)."""
    lg = lg.astype(F32)
    finite = jnp.isfinite(lg)
    return jnp.where(finite, lg, -1e30), ~jnp.all(finite, axis=-1)


def _is_paged_leaf(x) -> bool:
    return isinstance(x, paged.PagedKVCache)


def _caches_table_view(caches: "Caches", rows):
    """View of paged ``caches`` whose block tables hold only the batch
    slots ``rows`` (a traced [] or [m] int32 — an admission wave): pools
    are shared, so subset-row prefill writes scatter into the full pool
    while reads see only those rows' pages.  Stacked pattern caches
    gather along their batch axis (second-to-last of the
    [R, B, max_pages] table)."""
    rows = jnp.atleast_1d(jnp.asarray(rows, jnp.int32))
    def one(c):
        if not _is_paged_leaf(c):
            return c
        tbl = jnp.take(c.block_table, rows, axis=c.block_table.ndim - 2)
        return paged.PagedKVCache(c.k_pool, c.v_pool, tbl)
    return jax.tree.map(one, caches, is_leaf=_is_paged_leaf)


def _caches_adopt_tables(new: "Caches", orig: "Caches"):
    """Updated pools from ``new``, block tables from ``orig`` (undo a
    row view after a single-row prefill chunk)."""
    def two(n, o):
        if not _is_paged_leaf(n):
            return n
        return paged.PagedKVCache(n.k_pool, n.v_pool, o.block_table)
    return jax.tree.map(two, new, orig, is_leaf=_is_paged_leaf)


def caches_with_table(caches: "Caches", table):
    """Swap a fresh [B, max_pages] block table into every paged layer
    cache (stacked pattern caches broadcast it over their repeat axis) —
    the serving loop's admission/recycling hook.  Tables are traced
    values, so swapping between compiled steps never retraces."""
    table = jnp.asarray(table, jnp.int32)
    def one(c):
        if not _is_paged_leaf(c):
            return c
        return paged.PagedKVCache(c.k_pool, c.v_pool,
                                  jnp.broadcast_to(table,
                                                   c.block_table.shape))
    return jax.tree.map(one, caches, is_leaf=_is_paged_leaf)


def _norm(x, p, cfg: ModelConfig):
    if cfg.norm == "layernorm":
        return layernorm(x, p["g"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["g"], cfg.norm_eps)


def _norm_params(cfg: ModelConfig, dtype):
    p = {"g": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((cfg.d_model,), dtype)
    return p


# ---------------------------------------------------------------------------
# layer init
# ---------------------------------------------------------------------------
def init_layer(key, spec: LayerSpec, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 6)
    p: dict = {"norm1": _norm_params(cfg, dtype)}
    if spec.mixer == "gqa":
        p["attn"] = attn.gqa_params(ks[0], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.head_dim, dtype,
                                    qk_norm=spec.qk_norm)
    elif spec.mixer == "mla":
        p["attn"] = attn.mla_params(
            ks[0], cfg.d_model, cfg.n_heads, q_lora=cfg.q_lora,
            kv_lora=cfg.kv_lora, nope_dim=cfg.nope_dim,
            rope_dim=cfg.rope_dim, v_head_dim=cfg.v_head_dim, dtype=dtype)
    elif spec.mixer == "mamba2":
        p["attn"] = ssm.mamba2_params(ks[0], cfg.mamba, dtype)
    elif spec.mixer == "mlstm":
        p["attn"] = ssm.mlstm_params(ks[0], cfg.mlstm, dtype)
    elif spec.mixer == "slstm":
        p["attn"] = ssm.slstm_params(ks[0], cfg.slstm, dtype)
    elif spec.mixer in ("shared_attn", "none"):
        pass  # shared params live at top level / no mixer
    else:
        raise ValueError(spec.mixer)

    if spec.cross_attn:
        p["xattn"] = attn.gqa_params(ks[1], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv_heads, cfg.head_dim, dtype)
        p["norm_x"] = _norm_params(cfg, dtype)

    if spec.ffn in ("swiglu", "gelu"):
        p["mlp"] = mlp_params(ks[2], cfg.d_model, cfg.d_ff, dtype,
                              kind=spec.ffn if spec.ffn == "swiglu" else "gelu")
        p["norm2"] = _norm_params(cfg, dtype)
    elif spec.ffn == "moe":
        p["mlp"] = moe_mod.moe_params(ks[2], cfg.d_model, cfg.moe, dtype)
        p["norm2"] = _norm_params(cfg, dtype)
    if spec.post_norms:
        p["post1"] = _norm_params(cfg, dtype)
        if spec.ffn != "none":
            p["post2"] = _norm_params(cfg, dtype)
    return p


def init_shared_block(key, cfg: ModelConfig, dtype):
    """zamba2: one attention+MLP block whose weights are reused at every
    shared_attn position."""
    sb = cfg.shared_block
    ks = jax.random.split(key, 2)
    p = {"norm1": _norm_params(cfg, dtype),
         "attn": attn.gqa_params(ks[0], cfg.d_model, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.head_dim, dtype),
         "norm2": _norm_params(cfg, dtype),
         "mlp": mlp_params(ks[1], cfg.d_model, cfg.d_ff, dtype,
                           kind=sb.ffn if sb.ffn == "swiglu" else "gelu")}
    return p


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def init_layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                     max_len: int, policy: PrecisionPolicy,
                     page_table=None, n_pages: Optional[int] = None):
    kv_dtype = attn.kv_store_dtype(policy)
    c: dict = {}
    if spec.mixer in ("gqa", "shared_attn"):
        if cfg.paged_kv:
            c["kv"] = paged.init_paged_kv_cache(
                batch, cfg.n_kv_heads, max_len, cfg.page_size, cfg.head_dim,
                kv_dtype, block_table=page_table, n_pages=n_pages)
        else:
            c["kv"] = attn.init_kv_cache(batch, cfg.n_kv_heads, max_len,
                                         cfg.head_dim, kv_dtype)
    elif spec.mixer == "mla":
        c["kv"] = attn.init_mla_cache(batch, max_len, cfg.kv_lora,
                                      cfg.rope_dim, kv_dtype)
    elif spec.mixer == "mamba2":
        c["kv"] = ssm.init_mamba2_cache(batch, cfg.mamba, kv_dtype)
    elif spec.mixer == "mlstm":
        c["kv"] = ssm.init_mlstm_cache(batch, cfg.mlstm, kv_dtype)
    elif spec.mixer == "slstm":
        c["kv"] = ssm.init_slstm_cache(batch, cfg.slstm, kv_dtype)
    if spec.cross_attn:
        enc_len = cfg.encoder.n_frames
        c["xkv"] = attn.init_kv_cache(batch, cfg.n_kv_heads, enc_len,
                                      cfg.head_dim, kv_dtype)
    return c


class Caches(NamedTuple):
    prefix: Tuple
    pattern: Any          # stacked [R, ...] pytree
    suffix: Tuple


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                policy: PrecisionPolicy, page_table=None,
                n_pages: Optional[int] = None) -> Caches:
    """``page_table`` / ``n_pages`` (paged mode): every attention layer's
    ``PagedKVCache`` adopts the SAME [B, max_pages] table (allocation is
    symmetric across layers — each layer's pool grows identically), each
    with its own page pool.  ``None`` builds the identity (unshared)
    table."""
    mk = lambda spec: init_layer_cache(spec, cfg, batch, max_len, policy,
                                       page_table=page_table,
                                       n_pages=n_pages)
    pattern_one = tuple(mk(s) for s in cfg.pattern)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.repeats,) + x.shape),
        pattern_one)
    return Caches(prefix=tuple(mk(s) for s in cfg.prefix),
                  pattern=stacked,
                  suffix=tuple(mk(s) for s in cfg.suffix))


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------
_EXPERT_W = ("w_gate", "w_up", "w_down")


def apply_layer(x, p, spec: LayerSpec, cfg: ModelConfig,
                policy: PrecisionPolicy, *, positions, mesh=None,
                cache=None, cache_pos=None, enc_states=None,
                shared_params=None, decode: bool = False, kv_len=None,
                esc_fmts=None, kv_levels=None, kv_scale=None,
                verify: bool = False, serving: bool = False,
                experts=None):
    """Returns (x, new_cache, aux_loss) — with a fourth element
    ``kv_flags`` [B, 2] (per-row OF/UF write-flag counts) when
    ``esc_fmts`` is given (escalation write path; GQA mixers only, other
    mixers contribute zeros).  ``kv_len``/``cache_pos`` may be
    per-sequence [B] vectors (ragged batches) — attention mixers mask and
    write per row; SSM mixers have no length axis and ignore them.
    ``serving``/``experts``: see ``moe.moe_block`` (under ``serving`` a
    MoE layer's aux is the number of experts it read)."""
    aux = jnp.zeros((), F32)
    new_cache: dict = {}
    kv_flags = None
    rs = cfg.residual_scale

    ap = shared_params if spec.mixer == "shared_attn" else p
    h = _norm(x, ap["norm1"], cfg)
    kv_cache = cache.get("kv") if cache else None

    if spec.mixer in ("gqa", "shared_attn"):
        esc_kw = ({} if esc_fmts is None else
                  dict(esc_fmts=esc_fmts, kv_levels=kv_levels,
                       kv_scale=kv_scale))
        r = attn.gqa_attention(
            h, ap["attn"], policy, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            positions=positions, causal=True, window=spec.window,
            attn_softcap=spec.attn_softcap, rope_theta=cfg.rope_theta,
            qk_norm=spec.qk_norm, norm_eps=cfg.norm_eps,
            cache=kv_cache, cache_pos=cache_pos, use_rope=spec.use_rope,
            chunk=cfg.attn_chunk, windowed_slice=cfg.windowed_slice,
            decode_backend=cfg.decode_backend,
            prefill_backend=cfg.prefill_backend, kv_len=kv_len, mesh=mesh,
            verify=verify, **esc_kw)
        if esc_fmts is not None:
            mix, nc, kv_flags = r
        else:
            mix, nc = r
    elif spec.mixer == "mla":
        mix, nc = attn.mla_attention(
            h, ap["attn"], policy, n_heads=cfg.n_heads, nope_dim=cfg.nope_dim,
            rope_dim=cfg.rope_dim, v_head_dim=cfg.v_head_dim,
            positions=positions, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, cache=kv_cache, cache_pos=cache_pos,
            chunk=cfg.attn_chunk, prefill_backend=cfg.prefill_backend,
            kv_len=kv_len)
    elif spec.mixer == "mamba2":
        mix, nc = ssm.mamba2_mix(h, ap["attn"], cfg.mamba, policy,
                                 cache=kv_cache)
    elif spec.mixer == "mlstm":
        mix, nc = ssm.mlstm_mix(h, ap["attn"], cfg.mlstm, policy,
                                cache=kv_cache)
    elif spec.mixer == "slstm":
        mix, nc = ssm.slstm_mix(h, ap["attn"], cfg.slstm, policy,
                                cache=kv_cache)
    elif spec.mixer == "none":
        mix, nc = jnp.zeros_like(x), None
    else:
        raise ValueError(spec.mixer)

    if nc is not None:
        new_cache["kv"] = nc
    if spec.post_norms:
        mix = _norm(mix, p["post1"], cfg)
    x = x + rs * mix

    if spec.cross_attn:
        hx = _norm(x, p["norm_x"], cfg)
        if enc_states is not None:
            # prefill / train: compute cross K/V from encoder states
            mixx, xkv = attn.gqa_attention(
                hx, p["xattn"], policy, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                positions=positions, causal=False, use_rope=False,
                kv_states=enc_states,
                cache=cache.get("xkv") if cache else None, cache_pos=0,
                mesh=mesh)
        else:
            # decode: attend against the cached cross K/V
            mixx = attn.cross_attend_cached(
                hx, p["xattn"], cache["xkv"], policy, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim)
            xkv = cache["xkv"]
        if cache is not None:
            new_cache["xkv"] = xkv if xkv is not None else cache["xkv"]
        x = x + rs * mixx

    if spec.ffn != "none":
        fp = shared_params if spec.mixer == "shared_attn" else p
        h2 = _norm(x, fp["norm2"], cfg)
        if spec.ffn == "swiglu" or (spec.mixer == "shared_attn"
                                    and cfg.shared_block.ffn == "swiglu"):
            with jax.named_scope("mlp"):
                f = swiglu(h2, fp["mlp"]["gate"], fp["mlp"]["up"],
                           fp["mlp"]["down"], policy)
        elif spec.ffn == "gelu":
            with jax.named_scope("mlp"):
                f = gelu_mlp(h2, fp["mlp"]["up"], fp["mlp"]["b_up"],
                             fp["mlp"]["down"], fp["mlp"]["b_down"], policy)
        elif spec.ffn == "moe":
            f, aux = moe_mod.moe_block(h2, fp["mlp"], cfg.moe, policy,
                                       mesh=mesh, serving=serving,
                                       experts=experts)
        else:
            raise ValueError(spec.ffn)
        if spec.post_norms:
            f = _norm(f, p["post2"], cfg)
        x = x + rs * f
    if esc_fmts is not None:
        if kv_flags is None:
            kv_flags = jnp.zeros((x.shape[0], 2), jnp.int32)
        return x, (new_cache if new_cache else None), aux, kv_flags
    return x, (new_cache if new_cache else None), aux


# ---------------------------------------------------------------------------
# whisper-style encoder
# ---------------------------------------------------------------------------
def init_encoder(key, cfg: ModelConfig, dtype):
    e = cfg.encoder
    ks = jax.random.split(key, e.n_layers + 2)
    head_dim = cfg.d_model // e.n_heads
    layers = []
    for i in range(e.n_layers):
        kk = jax.random.split(ks[i], 2)
        layers.append({
            "norm1": _norm_params(cfg, dtype),
            "attn": attn.gqa_params(kk[0], cfg.d_model, e.n_heads,
                                    e.n_heads, head_dim, dtype),
            "norm2": _norm_params(cfg, dtype),
            "mlp": mlp_params(kk[1], cfg.d_model, e.d_ff, dtype, kind="gelu"),
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {"layers": stacked,
            "pos": (jax.random.normal(ks[-1], (e.n_frames, cfg.d_model), F32)
                    * 0.01).astype(dtype),
            "norm_f": _norm_params(cfg, dtype)}


def encode(frame_embeds, enc_params, cfg: ModelConfig,
           policy: PrecisionPolicy):
    e = cfg.encoder
    head_dim = cfg.d_model // e.n_heads
    x = frame_embeds + enc_params["pos"].astype(frame_embeds.dtype)
    positions = jnp.arange(x.shape[1])

    def body(h, lp):
        a, _ = attn.gqa_attention(
            _norm(h, lp["norm1"], cfg), lp["attn"], policy,
            n_heads=e.n_heads, n_kv_heads=e.n_heads, head_dim=head_dim,
            positions=positions, causal=False, use_rope=False)
        h = h + a
        f = gelu_mlp(_norm(h, lp["norm2"], cfg), lp["mlp"]["up"],
                     lp["mlp"]["b_up"], lp["mlp"]["down"],
                     lp["mlp"]["b_down"], policy)
        return h + f, None

    x, _ = jax.lax.scan(body, x, enc_params["layers"],
                        unroll=True if cfg.unroll_scan else 1)
    return _norm(x, enc_params["norm_f"], cfg)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    policy: PrecisionPolicy

    def with_cfg(self, **overrides) -> "Model":
        """Copy of this model with config fields replaced (e.g.
        ``model.with_cfg(decode_backend="pallas")``)."""
        return dataclasses.replace(
            self, cfg=dataclasses.replace(self.cfg, **overrides))

    # -- init ------------------------------------------------------------
    def init(self, key, mesh=None) -> dict:
        """Random parameters from ``key``, built by ONE jitted program so
        only its outputs are ever materialized (no per-layer copies live
        beside the stacked ones).  With ``mesh``, every parameter is
        created directly in its ``models.sharding.param_specs`` layout over
        the mesh's ``model`` axis — no device ever holds a whole-model
        copy of a sharded tensor."""
        shardings = None
        if mesh is not None:
            from .sharding import named, param_specs
            shapes = jax.eval_shape(self._init, key)
            shardings = named(mesh, param_specs(
                shapes, model_size=dict(mesh.shape).get("model", 1)))
        return jax.jit(self._init, out_shardings=shardings)(key)

    def _init(self, key) -> dict:
        cfg = self.cfg
        dtype = param_dtype(self.policy)
        n_keys = len(cfg.prefix) + len(cfg.suffix) + cfg.repeats * len(
            cfg.pattern) + 4
        ks = list(jax.random.split(key, n_keys))
        vpad = padded_vocab(cfg.vocab)
        params: dict = {
            "embed": embed_init(ks.pop(), vpad, cfg.d_model, dtype),
            "norm_f": _norm_params(cfg, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks.pop(), cfg.d_model, vpad,
                                           dtype)
        if cfg.max_seq:
            params["pos_embed"] = (jax.random.normal(
                ks.pop(), (cfg.max_seq, cfg.d_model), F32) * 0.01).astype(dtype)
        params["prefix"] = tuple(
            init_layer(ks.pop(), s, cfg, dtype) for s in cfg.prefix)
        params["suffix"] = tuple(
            init_layer(ks.pop(), s, cfg, dtype) for s in cfg.suffix)
        # stacked pattern params [R, ...]: one vmapped init over the
        # per-group keys writes the stacked arrays directly
        gkeys = jnp.stack([jnp.stack([ks.pop() for _ in cfg.pattern])
                           for _ in range(cfg.repeats)])
        params["pattern"] = jax.vmap(lambda kk: tuple(
            init_layer(kk[j], s, cfg, dtype)
            for j, s in enumerate(cfg.pattern)))(gkeys)
        if cfg.shared_block is not None:
            params["shared"] = init_shared_block(ks.pop(), cfg, dtype)
        if cfg.encoder is not None:
            params["encoder"] = init_encoder(ks.pop(), cfg, dtype)
        return params

    # -- embedding / unembedding ------------------------------------------
    def embed(self, params, tokens, frontend_embeds=None, *, pos_offset=0):
        cfg = self.cfg
        # activations start in the policy's storage dtype (a no-op for
        # params made by ``init``; widens narrower stored weights exactly)
        x = params["embed"][tokens].astype(
            param_dtype(get_policy(self.policy)))
        if cfg.emb_scale:
            x = (x.astype(F32) * cfg.emb_scale).astype(x.dtype)
        if cfg.frontend == "patch" and frontend_embeds is not None:
            # VLM stub: patch embeddings occupy the first K positions
            x = jax.lax.dynamic_update_slice(
                x, frontend_embeds.astype(x.dtype), (0, 0, 0))
        if cfg.max_seq:
            s = tokens.shape[1]
            if getattr(pos_offset, "ndim", 0) == 2:
                # speculative verify chunk: per-row, per-position offsets
                pe = params["pos_embed"][pos_offset]            # [B, S, d]
            elif getattr(pos_offset, "ndim", 0) >= 1:
                # ragged decode: each row reads its own learned position
                pe = params["pos_embed"][pos_offset][:, None]   # [B, 1, d]
            else:
                pe = jax.lax.dynamic_slice_in_dim(params["pos_embed"],
                                                  pos_offset, s, 0)
            x = x + pe.astype(x.dtype)
        return shard(x, residual_spec() if tokens.shape[1] > 1
                     else bspec(None, None))

    @property
    def vocab_out(self) -> int:
        """Logits width (padded vocab)."""
        return padded_vocab(self.cfg.vocab)

    def logits(self, params, x, policy=None):
        cfg = self.cfg
        policy = policy or self.policy
        w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        spec_str = "bsd,vd->bsv" if cfg.tie_embeddings else "bsd,dv->bsv"
        out_fmt = "fp16alt" if cfg.ce_dtype == "fp16alt" else "fp32"
        with jax.named_scope("head"):
            lg = tp.tp_einsum(spec_str, x, w, policy, out_fmt=out_fmt)
            lg = softcap(lg, cfg.logit_softcap)
            vpad = padded_vocab(cfg.vocab)
            if vpad != cfg.vocab:  # mask the pad tail (never predicted)
                lg = jnp.where(jnp.arange(vpad) < cfg.vocab, lg, -1e30)
            return shard(lg, bspec(None, "model"))

    # -- stacks ------------------------------------------------------------
    def _run_stack(self, params, x, *, positions, mesh=None, caches=None,
                   cache_pos=None, enc_states=None, remat: bool = False,
                   decode: bool = False, kv_len=None, esc_fmts=None,
                   kv_levels=None, kv_scale=None, verify: bool = False,
                   serving: bool = False):
        """``serving`` (set by the serving entry points ``prefill_chunk``,
        ``decode_step`` under ``decode_round``, ``verify_chunk``): MoE
        layers take the grouped expert kernel where ``moe.grouped_path``
        holds, and the third return is the number of experts read (int32,
        summed over layers) in place of the load-balancing loss.  The
        grouped path takes the pattern's expert weights whole, with the
        layer index riding the scan, so the scan never slices them."""
        cfg = self.cfg
        shared = params.get("shared")
        esc = esc_fmts is not None
        grouped = serving and moe_mod.grouped_path(cfg.moe, self.policy,
                                                   mesh)
        aux_total = jnp.zeros((), jnp.int32 if serving else F32)
        flags_total = (jnp.zeros((x.shape[0], 2), jnp.int32) if esc
                       else None)
        new_prefix, new_suffix = [], []

        def run_one(x, p, c, spec, experts=None):
            return apply_layer(x, p, spec, cfg, self.policy,
                               positions=positions, mesh=mesh, cache=c,
                               cache_pos=cache_pos, enc_states=enc_states,
                               shared_params=shared, decode=decode,
                               kv_len=kv_len, esc_fmts=esc_fmts,
                               kv_levels=kv_levels, kv_scale=kv_scale,
                               verify=verify, serving=serving,
                               experts=experts)

        def takes_grouped(spec):
            return (grouped and spec.ffn == "moe"
                    and spec.mixer != "shared_attn")

        def one_layer_experts(p, spec):
            # an unstacked layer's experts as a stack of one
            if not takes_grouped(spec):
                return None
            return tuple(p["mlp"][n][None] for n in _EXPERT_W) + (0,)

        for i, spec in enumerate(cfg.prefix):
            c = caches.prefix[i] if caches else None
            p = params["prefix"][i]
            r = run_one(x, p, c, spec, one_layer_experts(p, spec))
            x, nc, aux = r[:3]
            new_prefix.append(nc)
            aux_total += aux.astype(aux_total.dtype)
            if esc:
                flags_total += r[3]

        # grouped: the expert weights leave the scanned params and enter
        # the body whole; the scan carries the layer index instead
        pat_params = list(params["pattern"])
        pat_experts = [None] * len(cfg.pattern)
        for j, spec in enumerate(cfg.pattern):
            if takes_grouped(spec):
                mlp = dict(pat_params[j]["mlp"])
                pat_experts[j] = tuple(mlp.pop(n) for n in _EXPERT_W)
                pat_params[j] = {**pat_params[j], "mlp": mlp}
        n_groups = jax.tree.leaves(params["pattern"])[0].shape[0]

        def group_body(carry, xs):
            if esc:
                h, aux_acc, fl_acc = carry
            else:
                h, aux_acc = carry
            gp, gc, li = xs
            new_gc = []
            for j, spec in enumerate(cfg.pattern):
                c = gc[j] if gc is not None else None
                ex = (pat_experts[j] + (li,) if pat_experts[j] is not None
                      else None)
                r = run_one(h, gp[j], c, spec, ex)
                h, nc, aux = r[:3]
                aux_acc = aux_acc + aux.astype(aux_acc.dtype)
                new_gc.append(nc)
                if esc:
                    fl_acc = fl_acc + r[3]
            carry = ((h, aux_acc, fl_acc) if esc else (h, aux_acc))
            return carry, (tuple(new_gc) if caches is not None else None)

        if remat and cfg.remat_policy == "full":
            body = jax.checkpoint(group_body)
        elif remat and cfg.remat_policy == "dots":
            body = jax.checkpoint(
                group_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        else:  # "none" or remat=False: save everything
            body = group_body
        pat_caches = caches.pattern if caches is not None else None
        carry0 = ((x, aux_total, flags_total) if esc
                  else (x, aux_total))
        fc, new_pat = jax.lax.scan(
            body, carry0,
            (tuple(pat_params), pat_caches,
             jnp.arange(n_groups, dtype=jnp.int32)),
            unroll=True if cfg.unroll_scan else 1)
        if esc:
            x, aux_total, flags_total = fc
        else:
            x, aux_total = fc

        for i, spec in enumerate(cfg.suffix):
            c = caches.suffix[i] if caches else None
            p = params["suffix"][i]
            r = run_one(x, p, c, spec, one_layer_experts(p, spec))
            x, nc, aux = r[:3]
            new_suffix.append(nc)
            aux_total += aux.astype(aux_total.dtype)
            if esc:
                flags_total += r[3]

        new_caches = (Caches(tuple(new_prefix), new_pat, tuple(new_suffix))
                      if caches is not None else None)
        if esc:
            return x, new_caches, aux_total, flags_total
        return x, new_caches, aux_total

    # -- entry points -------------------------------------------------------
    def forward_train(self, params, tokens, labels, *, frontend_embeds=None,
                      mesh=None, remat: bool = True, aux_coef: float = 0.01,
                      loss_chunk: int = 1024):
        """[B,S] -> scalar LM loss (+ MoE aux)."""
        cfg = self.cfg
        enc_states = None
        if cfg.encoder is not None:
            enc_states = encode(frontend_embeds, params["encoder"], cfg,
                                self.policy)
        x = self.embed(params, tokens,
                       frontend_embeds if cfg.frontend == "patch" else None)
        positions = jnp.arange(tokens.shape[1])
        x, _, aux = self._run_stack(params, x, positions=positions, mesh=mesh,
                                    enc_states=enc_states, remat=remat)
        x = _norm(x, params["norm_f"], cfg)
        loss = self.chunked_ce(params, x, labels, chunk=loss_chunk)
        return loss + aux_coef * aux

    def chunked_ce(self, params, x, labels, *, chunk: int = 1024):
        """Cross-entropy without materializing [B,S,V]: scan over S-chunks."""
        cfg = self.cfg
        b, s, d = x.shape
        chunk = min(chunk, s)
        nchunks = -(-s // chunk)
        pad = nchunks * chunk - s
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        xc = jnp.moveaxis(x.reshape(b, nchunks, chunk, d), 1, 0)
        lc = jnp.moveaxis(labels.reshape(b, nchunks, chunk), 1, 0)

        def chunk_loss(carry, xs):
            xi, li = xs
            # [B,c,V]; bf16 under ce_dtype=fp16alt (stats below stay f32)
            lg = self.logits(params, xi).astype(F32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            mask = li >= 0
            li_safe = jnp.maximum(li, 0)
            gold = jnp.take_along_axis(lg, li_safe[..., None],
                                       axis=-1)[..., 0]
            nll = jnp.where(mask, lse - gold, 0.0)
            return (carry[0] + nll.sum(), carry[1] + mask.sum()), None

        (tot, cnt), _ = jax.lax.scan(
            chunk_loss, (jnp.zeros((), F32), jnp.zeros((), jnp.int32)),
            (xc, lc))
        return tot / jnp.maximum(cnt, 1)

    def prefill(self, params, tokens, *, max_len: int, frontend_embeds=None,
                mesh=None, prompt_lens=None, page_table=None,
                n_pages: Optional[int] = None):
        """Consume a prompt, build caches sized ``max_len``.

        ``prompt_lens`` ([B] int32) serves a RAGGED batch: ``tokens`` is
        right-padded to a shared width, each row's live prompt is its first
        ``prompt_lens[b]`` tokens.  Attention masks keys past each row's
        own length (the Pallas prefill kernel early-outs there — work
        proportional to the row's length), pad-slot K/V lands in cache
        slots the per-row decode ``kv_len`` keeps dead, and the returned
        logits are each row's LAST LIVE position's (not the pad tail's).

        Paged KV (``cfg.paged_kv``): caches become page pools + block
        tables (``models.paged``).  ``page_table`` ([B, max_pages] int32,
        a traced value — default: the identity/unshared table) lets rows
        alias pages, e.g. a shared prompt prefix stored once; aliasing
        rows must write identical values into shared pages, which a common
        prefix does by construction.  ``n_pages`` sizes the pools (static;
        default ``B * max_pages``, the unshared worst case).  Attention-
        mixer archs only: recurrent state has no page axis, and the
        whisper cross-attention cache stays contiguous by design.
        """
        cfg = self.cfg
        if cfg.paged_kv:
            why = cfg.paged_unsupported_reason()
            if why is not None:
                raise ValueError(
                    f"paged_kv is unsupported for {cfg.name}: {why} cannot "
                    f"page a contiguous-state cache (attention archs only)")
        elif page_table is not None:
            raise ValueError("page_table given but cfg.paged_kv is off")
        if prompt_lens is not None:
            # recurrent mixers have no length axis to mask: pad embeddings
            # would enter the state scan and silently corrupt every later
            # decode step — refuse rather than return padding-dependent
            # output (attention archs only, until SSM prefill masks inputs)
            ssm = sorted({s.mixer for s in cfg.layer_list()
                          if s.mixer in ("mamba2", "mlstm", "slstm")})
            if ssm:
                raise ValueError(
                    f"prompt_lens (ragged serving) is unsupported for "
                    f"{cfg.name}: {'/'.join(ssm)} mixers cannot mask pad "
                    f"tokens out of their recurrent state")
        enc_states = None
        if cfg.encoder is not None:
            enc_states = encode(frontend_embeds, params["encoder"], cfg,
                                self.policy)
        caches = init_caches(cfg, tokens.shape[0], max_len, self.policy,
                             page_table=page_table, n_pages=n_pages)
        x = self.embed(params, tokens,
                       frontend_embeds if cfg.frontend == "patch" else None)
        positions = jnp.arange(tokens.shape[1])
        x, caches, _ = self._run_stack(params, x, positions=positions,
                                       mesh=mesh, caches=caches, cache_pos=0,
                                       enc_states=enc_states,
                                       kv_len=prompt_lens)
        x = _norm(x, params["norm_f"], cfg)
        if prompt_lens is None:
            xl = x[:, -1:]
        else:
            last = (jnp.asarray(prompt_lens, jnp.int32) - 1)[:, None, None]
            xl = jnp.take_along_axis(x, last, axis=1)     # [B, 1, d]
        lg = self.logits(params, xl).astype(F32)
        return lg, caches

    def generate(self, params, tokens, *, gen_len: int,
                 max_len: Optional[int] = None, frontend_embeds=None,
                 mesh=None, return_logits: bool = False,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, key=None,
                 prompt_lens=None, stop_token: Optional[int] = None,
                 page_table=None, n_pages: Optional[int] = None,
                 repetition_penalty: Optional[float] = None,
                 presence_penalty: Optional[float] = None,
                 guard_nonfinite: bool = False):
        """Prefill + decode of ``gen_len`` tokens as ONE compiled program:
        the decode loop is a ``lax.scan`` over ``decode_step``, so the whole
        generation costs a single dispatch instead of one per token.

        The cache write index and the attention ``kv_len`` are traced scan
        carries — decode_step (and the Pallas decode kernel, which takes
        ``kv_len`` as a dynamic input) compile exactly once.

        Sampling: ``temperature > 0`` enables temperature / top-k / top-p
        sampling (``sample_token``) with the PRNG ``key`` threaded through
        the scan carry (split once per step).  The default ``temperature=0``
        is greedy argmax, bit-identical to the pre-sampling path — the
        sampling knobs are static, so the greedy graph carries no PRNG
        state at all.

        Ragged serving: ``prompt_lens`` ([B] int32) says row ``b``'s live
        prompt is ``tokens[b, :prompt_lens[b]]`` (right-padded batch).  The
        write index becomes a per-row vector — each row decodes from its
        own length, and the Pallas kernels prune each row's KV walk there.
        Differing length vectors reuse one compiled program (they are
        traced values).

        Paged KV: under ``cfg.paged_kv`` the caches riding the scan carry
        are page pools + block tables (see ``prefill``; ``page_table`` /
        ``n_pages`` pass through).  Decode-step writes scatter through the
        table and decode attention dereferences it — the write index /
        ``kv_len`` plumbing below is IDENTICAL either way, and since
        tables are traced, page churn between calls never retraces.

        EOS early-exit: with ``stop_token`` set, a per-row ``done`` mask
        rides the scan carry.  A finished row's outputs are frozen to
        ``stop_token``, and its live attention length is frozen at the
        step it finished — subsequent steps' K/V writes land in cache slots
        past that length, which every attention mask treats as dead, so the
        live cache is effectively frozen too (SSM-mixer layers in hybrid
        archs keep updating their recurrent state; their outputs are
        discarded the same way).

        Penalties: ``repetition_penalty`` / ``presence_penalty``
        (``apply_penalties``) discount tokens already seen — a per-row
        count histogram (prompt + emitted tokens, pad slots excluded)
        rides the loop carry and is applied to the raw logits before
        temperature / top-k / top-p at every step, composing with greedy
        (penalized argmax) and EOS freezing alike.  The default (both
        ``None``) carries no count state — greedy stays bit-identical.

        ``guard_nonfinite=True`` routes every sampling site (prefill
        last-token logits included) through ``sanitize_logits`` and
        appends a per-row [B] int32 count of guarded steps to the return —
        the caller's fail-fast hook (raise when any count is nonzero) or
        the fault harness's mask-and-flag accounting.  Finite logits are
        untouched, so guarded greedy decoding stays bit-identical; the
        default carries no guard state at all.

        Returns ``(gen_tokens [B, gen_len], logits)`` where ``logits`` is
        ``[B, gen_len, V]`` (prefill last-token logits followed by each
        step's) when ``return_logits`` else None; ``guard_nonfinite``
        appends the per-row guard counts.
        """
        b, prompt_len = tokens.shape
        max_len = max_len if max_len is not None else prompt_len + gen_len
        do_sample = temperature is not None and temperature > 0.0
        use_stop = stop_token is not None
        use_pen = ((repetition_penalty is not None
                    and repetition_penalty != 1.0)
                   or (presence_penalty is not None
                       and presence_penalty != 0.0))
        pick = functools.partial(sample_token, temperature=temperature,
                                 top_k=top_k, top_p=top_p)
        pen = functools.partial(apply_penalties,
                                repetition_penalty=repetition_penalty,
                                presence_penalty=presence_penalty)
        lg0, caches = self.prefill(params, tokens, max_len=max_len,
                                   frontend_embeds=frontend_embeds,
                                   mesh=mesh, prompt_lens=prompt_lens,
                                   page_table=page_table, n_pages=n_pages)
        cnt0 = (token_counts(tokens, self.vocab_out, prompt_lens)
                if use_pen else None)
        guard = guard_nonfinite
        lg0v = lg0[:, -1]
        bad0 = None
        if guard:
            lg0v, bad0 = sanitize_logits(lg0v)
        lg0p = pen(lg0v, cnt0) if use_pen else lg0v
        if do_sample:
            key = jax.random.key(0) if key is None else key
            key, k0 = jax.random.split(key)
            tok0 = pick(lg0p, k0)[:, None]
        else:
            tok0 = jnp.argmax(lg0p, -1).astype(jnp.int32)[:, None]

        # per-row write index when ragged, the shared scalar otherwise —
        # it ALWAYS advances (done rows write into dead slots, see above)
        pos0 = (jnp.asarray(prompt_lens, jnp.int32) if prompt_lens is not None
                else jnp.asarray(prompt_len, jnp.int32))
        if use_stop:
            done0 = tok0[:, 0] == stop_token
            tok0 = jnp.where(done0[:, None], stop_token, tok0)
        if use_pen:
            cnt0 = _bump_counts(cnt0, tok0)

        def body(carry, _):
            tok, c, pos = carry[:3]
            rest = list(carry[3:])
            lens = done = ky = cnt = None
            if use_stop:
                lens, done = rest.pop(0), rest.pop(0)
            if use_pen:
                cnt = rest.pop(0)
            if do_sample:
                ky, step_key = jax.random.split(rest.pop(0))
            bad_acc = rest.pop(0) if guard else None
            # a done row's live window stays at the length it finished with
            attend = jnp.where(done, lens, pos + 1) if use_stop else None
            lg, c = self.decode_step(params, tok, c, pos, mesh=mesh,
                                     kv_len=attend)
            lgv = lg[:, -1]
            bad = None
            if guard:
                lgv, bad = sanitize_logits(lgv)
            lgp = pen(lgv, cnt) if use_pen else lgv
            if do_sample:
                nxt = pick(lgp, step_key)[:, None]
            else:
                nxt = jnp.argmax(lgp, -1).astype(jnp.int32)[:, None]
            nc = [None, c, pos + 1]
            if use_stop:
                nxt = jnp.where(done[:, None], stop_token, nxt)
                nc += [jnp.where(done, lens, pos + 1), done
                       | (nxt[:, 0] == stop_token)]
            nc[0] = nxt
            if use_pen:
                nc.append(_bump_counts(cnt, nxt))
            if do_sample:
                nc.append(ky)
            if guard:
                live_bad = (bad & ~done) if use_stop else bad
                nc.append(bad_acc + live_bad.astype(jnp.int32))
            ys = (nxt[:, 0], lg[:, 0]) if return_logits else (nxt[:, 0],)
            return tuple(nc), ys

        init = [tok0, caches, pos0]
        if use_stop:
            # live length entering the first step: the prompt only (tok0's
            # K/V is written by that step); broadcast for uniform batches
            init += [jnp.broadcast_to(pos0, (b,)), done0]
        if use_pen:
            init.append(cnt0)
        if do_sample:
            init.append(key)
        if guard:
            init.append(bad0.astype(jnp.int32))

        fc, ys = jax.lax.scan(body, tuple(init), None, length=gen_len - 1)
        gen = jnp.concatenate([tok0, ys[0].swapaxes(0, 1)], axis=1)
        lgs = (jnp.concatenate([lg0, jnp.moveaxis(ys[1], 0, 1)], axis=1)
               if return_logits else None)
        out = (gen, lgs)
        if guard:
            out += (fc[-1],)
        return out

    def decode_step(self, params, token, caches: Caches, pos, *, mesh=None,
                    kv_len=None, esc_fmts=None, kv_levels=None,
                    kv_scale=None, serving: bool = False):
        """One decode step: token [B,1], pos scalar -> (logits [B,1,V],
        caches).  ``pos`` may be a per-sequence [B] vector (ragged batch):
        each row writes its K/V at — and takes its rope position from — its
        OWN index.  ``kv_len`` overrides the attended live length
        (scalar-or-vector; default ``pos + 1``) so EOS-frozen rows keep
        writing into dead cache slots without growing their live window.

        ``esc_fmts``/``kv_levels``/``kv_scale`` (escalation write path, see
        ``attention.quantize_kv_rows``) append the per-row OF/UF write-flag
        counts ``kv_flags`` [B, 2] to the return.  ``serving=True`` (from
        ``decode_round``) takes the serving expert path and appends, last,
        the number of experts read (``_run_stack``)."""
        cfg = self.cfg
        x = self.embed(params, token, pos_offset=pos if cfg.max_seq else 0)
        if getattr(pos, "ndim", 0) >= 1:
            positions = pos[:, None, None]     # broadcastable to [B, H, 1]
        else:
            positions = pos + jnp.arange(1)
        r = self._run_stack(params, x, positions=positions,
                            mesh=mesh, caches=caches,
                            cache_pos=pos, decode=True,
                            kv_len=kv_len, esc_fmts=esc_fmts,
                            kv_levels=kv_levels, kv_scale=kv_scale,
                            serving=serving)
        x, caches = r[0], r[1]
        x = _norm(x, params["norm_f"], cfg)
        lg = self.logits(params, x).astype(F32)
        ret = (lg, caches)
        if esc_fmts is not None:
            ret += (r[3],)
        return ret + (r[2],) if serving else ret

    # -- continuous-batching steps (launch/engine.py drives these) ---------
    def prefill_chunk(self, params, tokens, caches: Caches, *,
                      q_offset: int, row=None, chunk_lens=None, mesh=None,
                      esc_fmts=None, kv_levels=None):
        """Consume ONE prompt chunk into EXISTING caches — the chunked-
        prefill half of continuous batching (paged archs only: the chunk
        must read every EARLIER chunk's K/V back through the page pool,
        which is exactly the paged prefill read path).

        ``tokens`` [b, C]: the chunk, right-padded to a fixed width C so
        chunk calls share compiled programs.  ``q_offset``: the chunk's
        start position in the row — a STATIC int (it shapes the Pallas
        block schedule); schedulers step it in multiples of C, so at most
        ``max_prompt / C`` programs ever compile.  ``chunk_lens`` [b]: live
        tokens within this chunk (pad-tail K/V lands in dead slots that
        later real writes overwrite before they can ever be read).

        ``row``: traced [] or [m] int32 batch-slot indices — serve a
        SUBSET of a wider serving batch (an admission wave while other
        slots keep decoding; ``tokens``/``chunk_lens`` are then [m, C] /
        [m]): block tables are gathered to those rows, writes scatter
        into the SHARED pool through each row's own table entries, and
        the returned caches carry the original full-width tables.  Being
        traced, slot indices never retrace across admission events.

        Returns ``(logits [b, 1, V], caches)`` — each row's logits at its
        last live chunk position (the final chunk's logits seed the first
        generated token).  ``esc_fmts`` + ``kv_levels`` ([b] int32 rungs
        aligned to ``tokens`` rows — the caller gathers per-slot levels to
        the wave) route the chunk's cache writes through the escalation
        quantizer and append the per-row OF/UF flag counts [b, 2] to the
        return — a reingested row re-prefills AT its escalated rung."""
        cfg = self.cfg
        if not cfg.paged_kv:
            raise ValueError(
                "prefill_chunk requires cfg.paged_kv: a continuation chunk "
                "reads the prefix through the page pool (contiguous prefill "
                "attends only its own fresh K/V)")
        why = cfg.paged_unsupported_reason()
        if why is not None:
            raise ValueError(
                f"prefill_chunk is unsupported for {cfg.name}: {why} cannot "
                f"page a contiguous-state cache (attention archs only)")
        b, s = tokens.shape
        run = _caches_table_view(caches, row) if row is not None else caches
        x = self.embed(params, tokens, pos_offset=q_offset)
        positions = q_offset + jnp.arange(s)
        live = jnp.reshape(jnp.asarray(
            s if chunk_lens is None else chunk_lens, jnp.int32), (-1,))
        r = self._run_stack(params, x, positions=positions,
                            mesh=mesh, caches=run,
                            cache_pos=q_offset,
                            kv_len=q_offset + live,
                            esc_fmts=esc_fmts, kv_levels=kv_levels,
                            serving=True)
        x, run = r[0], r[1]
        x = _norm(x, params["norm_f"], cfg)
        last = (jnp.maximum(jnp.broadcast_to(live, (b,)), 1) - 1)[:, None,
                                                                  None]
        lg = self.logits(params, jnp.take_along_axis(x, last,
                                                     axis=1)).astype(F32)
        if row is not None:
            run = _caches_adopt_tables(run, caches)
        if esc_fmts is not None:
            return lg, run, r[3]
        return lg, run

    def decode_round(self, params, tok, caches: Caches, pos, *, lens, done,
                     stop_token: Optional[int] = None,
                     temperature: float = 0.0, top_k: Optional[int] = None,
                     top_p: Optional[float] = None, key=None, mesh=None,
                     counts=None, repetition_penalty: Optional[float] = None,
                     presence_penalty: Optional[float] = None,
                     poison=None, guard: bool = False, esc_fmts=None,
                     kv_levels=None, kv_scale=None):
        """ONE decode round over every batch slot of a continuous batch:
        ``decode_step`` at per-row write index ``pos``, attending each
        row's live window (``lens`` for done/idle rows, ``pos + 1`` for
        running ones), then sampling.  Done rows emit ``stop_token`` and
        keep writing into dead slots; idle slots (``lens == 0``) attend
        nothing and emit garbage the scheduler ignores.  All row state is
        traced — admission, page recycling and EOS churn between rounds
        never retrace.

        ``counts`` [B, V] + ``repetition_penalty``/``presence_penalty``
        apply the same seen-token discounts as ``generate`` (raw logits,
        before temperature/top-k/top-p); the caller owns count upkeep.
        ``poison`` (traced bool, fault injection) overwrites the round's
        logits with NaN; ``guard=True`` routes sampling through
        ``sanitize_logits`` — bit-identical on finite logits — and appends
        the per-row ``bad`` flag to the return.  ``esc_fmts``/``kv_levels``
        /``kv_scale`` (escalation write path) append the per-row OF/UF
        write-flag counts [B, 2].  Returns ``(next_tok [B,1], logits,
        caches, key[, bad][, kv_flags], experts_read)``; the SCHEDULER
        owns pos/lens/done advancement (see decode_burst for the compiled
        multi-round form)."""
        attend = jnp.where(done, lens, pos + 1)
        r = self.decode_step(params, tok, caches, pos, mesh=mesh,
                             kv_len=attend, esc_fmts=esc_fmts,
                             kv_levels=kv_levels, kv_scale=kv_scale,
                             serving=True)
        lg, caches = r[0], r[1]
        kv_flags = r[2] if esc_fmts is not None else None
        with jax.named_scope("sample"):
            lgv = lg[:, -1]
            if poison is not None:
                lgv = jnp.where(jnp.asarray(poison), jnp.nan, lgv)
            bad = None
            if guard:
                lgv, bad = sanitize_logits(lgv)
            if counts is not None:
                lgv = apply_penalties(lgv, counts,
                                      repetition_penalty=repetition_penalty,
                                      presence_penalty=presence_penalty)
            if temperature is not None and temperature > 0.0:
                key, sk = jax.random.split(jax.random.key(0)
                                           if key is None else key)
                nxt = sample_token(lgv, sk, temperature=temperature,
                                   top_k=top_k, top_p=top_p)[:, None]
            else:
                nxt = jnp.argmax(lgv, -1).astype(jnp.int32)[:, None]
            if stop_token is not None:
                nxt = jnp.where(done[:, None], stop_token, nxt)
        ret = (nxt, lg, caches, key)
        if guard:
            ret += (bad,)
        if esc_fmts is not None:
            ret += (kv_flags,)
        return ret + (r[-1],)

    def decode_burst(self, params, tok, caches: Caches, pos, lens, done,
                     limit, *, max_len: int, out_width: int, n_max,
                     exit_on_finish, stop_token: Optional[int] = None,
                     temperature: float = 0.0, top_k: Optional[int] = None,
                     top_p: Optional[float] = None, key=None, mesh=None,
                     counts=None, repetition_penalty: Optional[float] = None,
                     presence_penalty: Optional[float] = None,
                     poison_at=None, guard: bool = False, esc_fmts=None,
                     kv_levels=None, ovf_at=None, ovf_scale=None):
        """Up to ``n_max`` continuous-batching decode rounds as ONE
        compiled ``lax.while_loop`` — the engine's steady-state dispatch
        cost amortizes like the scan path's.

        Per-row carry: write index ``pos``, live length ``lens``, ``done``
        mask, and ``limit`` (the pos at which a row has emitted its whole
        budget: ``prompt_len + budget - 1``).  A row finishes when it
        emits ``stop_token`` or reaches its limit; its outputs freeze and
        its later writes land in dead slots (write index clamped inside
        ``max_len``).  The loop exits when every row is done, after
        ``n_max`` rounds (both always on), or — when ``exit_on_finish``
        (a TRACED int) is ``k > 0`` — the round the k-th running row
        finishes since burst entry, handing control back to the host
        scheduler so finished rows' pages can be freed and queued
        requests admitted that round (``k = 1``: react to every finish;
        ``k = 2``: batch admissions in waves, halving scheduler
        round-trips; ``0``: run to ``n_max``/all-done).  ``n_max``,
        ``exit_on_finish`` and all row state are traced: bursts of any
        shape share one compiled program.

        Robustness hooks (launch/engine.py): ``counts`` [B, V] rides the
        carry and applies ``repetition_penalty``/``presence_penalty`` at
        every round exactly like ``generate``'s count carry (the caller
        seeds the histogram and re-syncs it between bursts);
        ``poison_at`` (traced int, ``-1`` = never) NaN-poisons that
        relative round's logits — deterministic fault injection;
        ``guard=True`` masks non-finite logits before sampling and counts
        each live row's poisoned rounds.

        Numerical-health hooks: ``esc_fmts`` + ``kv_levels`` ([B] int32,
        constant within a burst — the host escalates between bursts) route
        every round's cache writes through the escalation quantizer; the
        per-row OF/UF write-flag counts accumulate in the carry (rounds a
        row is done contribute zero — same attribution rule as ``bad``)
        and ride back as ``kv_flags`` [B, 2].  ``ovf_at`` (traced int,
        ``-1`` = never) + ``ovf_scale`` multiply that relative round's K/V
        pre-quantization — deterministic overflow injection, the write-side
        twin of ``poison_at``.

        Returns ``(out [B, out_width], n_steps, tok, caches, pos, lens,
        done, key[, bad][, counts][, kv_flags], experts_read)`` —
        ``out[:, :n_steps]`` holds each round's emitted token per row
        (rows already done emit ``stop_token``/pad); ``bad`` [B] int32
        (when ``guard``) counts rounds a live row's logits went
        non-finite; ``counts`` (when penalties are active) is the
        advanced histogram; ``experts_read`` (int32) sums, over the
        rounds run and the layers, the experts whose weights each MoE
        layer read (0 for a model without experts)."""
        b = tok.shape[0]
        do_sample = temperature is not None and temperature > 0.0
        if do_sample and key is None:
            key = jax.random.key(0)
        use_pen = counts is not None and (
            (repetition_penalty is not None and repetition_penalty != 1.0)
            or (presence_penalty is not None and presence_penalty != 0.0))
        done0 = done
        pad = stop_token if stop_token is not None else -1
        out0 = jnp.full((b, out_width), pad, jnp.int32)
        n_max = jnp.asarray(n_max, jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        poison_at = (None if poison_at is None
                     else jnp.asarray(poison_at, jnp.int32))
        esc = esc_fmts is not None
        ovf_at = None if ovf_at is None else jnp.asarray(ovf_at, jnp.int32)

        wave = jnp.asarray(exit_on_finish, jnp.int32)

        def cond(c):
            i, done = c[0], c[6]
            more = (i < n_max) & ~jnp.all(done)
            newly = jnp.sum((done & ~done0).astype(jnp.int32))
            return more & ((wave == 0) | (newly < wave))

        def body(c):
            i, out, tok, caches, pos, lens, done, n_read = c[:8]
            extra = list(c[8:])
            cnt = extra.pop(0) if use_pen else None
            badc = extra.pop(0) if guard else None
            flacc = extra.pop(0) if esc else None
            scale = (jnp.where(i == ovf_at, ovf_scale, 1.0)
                     if ovf_at is not None else None)
            r = self.decode_round(
                params, tok, caches, pos, lens=lens, done=done,
                stop_token=stop_token, temperature=temperature,
                top_k=top_k, top_p=top_p,
                key=extra.pop(0) if do_sample else None, mesh=mesh,
                counts=cnt if use_pen else None,
                repetition_penalty=repetition_penalty,
                presence_penalty=presence_penalty,
                poison=(i == poison_at) if poison_at is not None else None,
                guard=guard, esc_fmts=esc_fmts, kv_levels=kv_levels,
                kv_scale=scale)
            nxt, _, caches, ky = r[:4]
            out = jax.lax.dynamic_update_slice(out, nxt, (zero, i))
            fin = done | (pos + 1 >= limit)
            if stop_token is not None:
                fin = fin | (nxt[:, 0] == stop_token)
            new_pos = jnp.where(done, pos,
                                jnp.minimum(pos + 1, max_len - 1))
            new_lens = jnp.where(done, lens, pos + 1)
            nc = (i + 1, out, nxt, caches, new_pos, new_lens, fin,
                  n_read + r[-1])
            if use_pen:
                nc += (_bump_counts(cnt, nxt),)
            if guard:
                # attribute poisoned rounds to rows live entering the round
                nc += (badc + (r[4] & ~done).astype(jnp.int32),)
            if esc:
                fl = r[4 + (1 if guard else 0)]
                nc += (flacc + fl * (~done).astype(jnp.int32)[:, None],)
            return nc + ((ky,) if do_sample else ())

        init = (zero, out0, tok, caches, pos, lens, done, zero)
        if use_pen:
            init += (counts,)
        if guard:
            init += (jnp.zeros((b,), jnp.int32),)
        if esc:
            init += (jnp.zeros((b, 2), jnp.int32),)
        if do_sample:
            init += (key,)
        fin = jax.lax.while_loop(cond, body, init)
        n, out, tok, caches, pos, lens, done, n_read = fin[:8]
        extra = list(fin[8:])
        cnt_out = extra.pop(0) if use_pen else None
        bad_out = extra.pop(0) if guard else None
        fl_out = extra.pop(0) if esc else None
        ret = (out, n, tok, caches, pos, lens, done,
               extra.pop(0) if do_sample else key)
        if guard:
            ret += (bad_out,)
        if use_pen:
            ret += (cnt_out,)
        if esc:
            ret += (fl_out,)
        return ret + (n_read,)

    # -- speculative decoding (draft k cheap, verify once, accept prefix) --
    def speculate_check(self):
        """Raise unless this arch supports speculative decoding: the
        verify read folds chunk queries through the decode attend path,
        which exists for GQA-family mixers only (recurrent state cannot
        roll back rejected tokens, and the MLA latent cache has no
        multi-query verify read yet)."""
        cfg = self.cfg
        bad = sorted({s.mixer for s in cfg.layer_list()
                      if s.mixer not in ("gqa", "shared_attn", "none")})
        if bad:
            raise ValueError(
                f"speculative decoding is unsupported for {cfg.name}: "
                f"{'/'.join(bad)} mixers cannot roll back rejected tokens")
        if cfg.encoder is not None or any(s.cross_attn
                                          for s in cfg.layer_list()):
            raise ValueError(
                f"speculative decoding is unsupported for {cfg.name}: "
                f"cross-attention decode has no verify read path")

    def draft_view(self, params, caches, draft_repeats,
                   draft_policy=None):
        """Layer-skip draft submodel: the SAME weights truncated to the
        first ``draft_repeats`` pattern groups (prefix/suffix layers kept
        — they are few and cheap), optionally under a narrower
        ``draft_policy`` for the matmuls.  Returns ``(model, params,
        caches)`` views; the stacked pattern leaves are sliced ``[:r]``,
        so the draft SHARES the target's cache pools for the layers it
        runs — its writes are discarded by the caller (verify rewrites
        every drafted position at every layer with target-precision
        values before any accepted read)."""
        cfg = self.cfg
        r = cfg.repeats if draft_repeats is None else draft_repeats
        r = max(0, min(int(r), cfg.repeats))
        dm = self
        dp, dc = params, caches
        if r < cfg.repeats:
            n_layers = (len(cfg.prefix) + len(cfg.suffix)
                        + r * len(cfg.pattern))
            dm = self.with_cfg(n_layers=n_layers)
            dp = dict(params)
            dp["pattern"] = jax.tree.map(lambda x: x[:r], params["pattern"])
            if caches is not None:
                dc = Caches(caches.prefix,
                            jax.tree.map(lambda x: x[:r], caches.pattern),
                            caches.suffix)
        if draft_policy is not None:
            dm = dataclasses.replace(dm, policy=draft_policy)
        return dm, dp, dc

    def verify_chunk(self, params, tokens, caches: Caches, pos, *,
                     kv_len, mesh=None, esc_fmts=None, kv_levels=None,
                     kv_scale=None):
        """Score a [B, S] candidate chunk at target precision through the
        DECODE read path — the speculative verify call.

        ``pos`` [B] (or scalar) is each row's write index for the chunk's
        first token; the chunk's K/V lands at ``pos .. pos+S-1`` (the same
        bytes S sequential decode steps would write), and ``kv_len``
        [B, S] gives each query position's live attend length (running
        rows: ``pos + i + 1``; EOS-frozen rows: their frozen length).
        Queries fold into the batch dimension inside attention
        (``gqa_attention(verify=True)``), so ``logits[:, i]`` is BITWISE
        the logits a plain ``decode_step`` would emit after consuming
        ``tokens[:, :i+1]`` — parity by construction, not by tolerance.
        Returns ``(logits [B, S, V], caches[, kv_flags])``."""
        cfg = self.cfg
        b, s = tokens.shape
        posv = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))
        offs = posv[:, None] + jnp.arange(s, dtype=jnp.int32)   # [B, S]
        x = self.embed(params, tokens, pos_offset=offs if cfg.max_seq else 0)
        r = self._run_stack(params, x, positions=offs[:, None, :],
                            mesh=mesh, caches=caches, cache_pos=posv,
                            decode=True, verify=True,
                            kv_len=jnp.broadcast_to(
                                jnp.asarray(kv_len, jnp.int32), (b, s)),
                            esc_fmts=esc_fmts, kv_levels=kv_levels,
                            kv_scale=kv_scale, serving=True)
        x, caches = r[0], r[1]
        x = _norm(x, params["norm_f"], cfg)
        lg = self.logits(params, x).astype(F32)
        if esc_fmts is not None:
            return lg, caches, r[3]
        return lg, caches

    def speculate_step(self, params, tok, caches: Caches, pos, *, lens,
                       done, limit, spec_k: int, draft_repeats=None,
                       k_rows=None, stop_token: Optional[int] = None,
                       mesh=None, guard: bool = False, esc_fmts=None,
                       kv_levels=None, kv_scale=None, poison=None,
                       draft_policy=None, _draft_fn=None):
        """ONE speculative round: draft ``spec_k`` tokens with the cheap
        pass, verify the whole chunk at target precision, accept the
        longest matching prefix plus the verify model's own next token.

        Greedy only — acceptance compares draft proposals against the
        verify argmax, so every accepted token (and the bonus token) is
        exactly what sequential greedy decode would have emitted; a wrong
        draft can only LOWER the accept count, never change the stream.
        Rollback is free: rejected positions sit at/past each row's new
        ``lens``, which every attention mask treats as dead, and the next
        round's chunk write covers them before they could become live.

        ``k_rows`` [B] (optional) caps each row's accepted DRAFTS
        (``0`` = that row runs plain single-token decode inside the
        speculative batch); EOS clamps acceptance at the first emitted
        ``stop_token``; ``limit`` clamps it at the row's budget.
        ``_draft_fn(tok, pos) -> [B, spec_k]`` overrides the draft pass —
        the fault/test hook for adversarial (e.g. never-matching) drafts.

        Returns ``(g [B, spec_k+1], n [B], tok, pos, lens, done,
        caches[, bad][, kv_flags])`` — ``g[:, :n[b]]`` are row b's
        emitted tokens this round (``n == 0`` for rows already done),
        ``bad`` [B] flags rows whose ACCEPTED logits went non-finite."""
        b = tok.shape[0]
        k1 = spec_k + 1
        pos = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(pos, jnp.int32), (-1,)), (b,))
        if _draft_fn is not None:
            drafts = jnp.asarray(_draft_fn(tok, pos), jnp.int32)
        elif spec_k == 0:
            drafts = jnp.zeros((b, 0), jnp.int32)
        else:
            dm, dp, dc = self.draft_view(params, caches, draft_repeats,
                                         draft_policy)

            def dstep(carry, _):
                dtok, dcc, dpos = carry
                attend = jnp.where(done, lens, dpos + 1)
                dlg, dcc = dm.decode_step(dp, dtok, dcc, dpos, mesh=mesh,
                                          kv_len=attend)
                nxt = jnp.argmax(dlg[:, -1], -1).astype(jnp.int32)[:, None]
                return (nxt, dcc, dpos + 1), nxt[:, 0]

            # draft writes ride dcc within the round (step i attends its
            # own earlier proposals) and are then DISCARDED: verify
            # rewrites pos..pos+k at every layer below
            _, dseq = jax.lax.scan(dstep, (tok, dc, pos), None,
                                   length=spec_k)
            drafts = dseq.swapaxes(0, 1)                       # [B, k]
        chunk = jnp.concatenate([tok, drafts], axis=1)         # [B, k+1]
        offs = pos[:, None] + jnp.arange(k1, dtype=jnp.int32)
        attend = jnp.where(done[:, None], lens[:, None], offs + 1)
        r = self.verify_chunk(params, chunk, caches, pos, kv_len=attend,
                              mesh=mesh, esc_fmts=esc_fmts,
                              kv_levels=kv_levels, kv_scale=kv_scale)
        lg, caches = r[0], r[1]
        kv_flags = r[2] if esc_fmts is not None else None
        if poison is not None:
            lg = jnp.where(jnp.asarray(poison), jnp.nan, lg)
        badm = None
        if guard:
            lg, badm = sanitize_logits(lg)                     # bad [B, k+1]
        g = jnp.argmax(lg, -1).astype(jnp.int32)               # [B, k+1]
        if spec_k:
            m = jnp.sum(jnp.cumprod(
                (drafts == g[:, :-1]).astype(jnp.int32), axis=1), axis=1)
        else:
            m = jnp.zeros((b,), jnp.int32)
        if k_rows is not None:
            m = jnp.minimum(m, jnp.asarray(k_rows, jnp.int32))
        n = m + 1
        if stop_token is not None:
            is_stop = g == stop_token
            fs = jnp.where(jnp.any(is_stop, 1),
                           jnp.argmax(is_stop, 1), k1).astype(jnp.int32)
            n = jnp.minimum(n, fs + 1)
        n = jnp.minimum(n, jnp.maximum(limit - pos, 1))
        n = jnp.where(done, 0, n).astype(jnp.int32)
        lastix = jnp.maximum(n - 1, 0)[:, None]
        last = jnp.take_along_axis(g, lastix, axis=1)
        new_tok = jnp.where(done[:, None], tok, last)
        new_pos = pos + n
        new_lens = jnp.where(done, lens, new_pos)
        new_done = done | (new_pos >= limit)
        if stop_token is not None:
            stopped = jnp.take_along_axis(g == stop_token, lastix,
                                          axis=1)[:, 0]
            new_done = new_done | (~done & stopped)
        ret = (g, n, new_tok, new_pos, new_lens, new_done, caches)
        if guard:
            # attribute non-finite logits to rows whose ACCEPTED positions
            # were sanitized (rejected drafts never reach the stream)
            acc = jnp.arange(k1)[None, :] < n[:, None]
            ret += (jnp.any(badm & acc, axis=1),)
        if esc_fmts is not None:
            ret += (kv_flags,)
        return ret

    def speculate_decode(self, params, tokens, *, gen_len: int,
                         spec_k: int, draft_repeats=None,
                         max_len: Optional[int] = None, prompt_lens=None,
                         stop_token: Optional[int] = None, page_table=None,
                         n_pages: Optional[int] = None, mesh=None,
                         draft_policy=None, _draft_fn=None,
                         return_stats: bool = False):
        """Speculative analog of greedy ``generate``: prefill, then a
        ``while_loop`` of ``speculate_step`` rounds, each emitting 1 to
        ``spec_k + 1`` tokens per row.  The emitted stream is bit-identical
        to ``generate(..., temperature=0)`` — same prompts, same
        ``stop_token`` freezing, same per-row budgets — regardless of how
        good or bad the draft is (accepted tokens are always the verify
        model's own argmax chain).

        ``max_len`` must leave ``spec_k`` slots of lookahead headroom past
        ``prompt + gen_len``: every round writes a full ``spec_k + 1``-wide
        chunk, and a clamped ``dynamic_update_slice`` near the cache edge
        would SHIFT the write window onto live slots.  ``return_stats``
        appends ``(rounds, emitted)`` int32 scalars (accept rate =
        ``emitted / (rounds * (spec_k + 1))`` over live-row rounds)."""
        self.speculate_check()
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        b, prompt_len = tokens.shape
        k1 = spec_k + 1
        need = prompt_len + gen_len + spec_k
        max_len = need if max_len is None else max_len
        if max_len < need:
            raise ValueError(
                f"speculative decoding needs max_len >= prompt + gen_len + "
                f"spec_k = {need} (draft lookahead headroom; a clamped "
                f"chunk write would corrupt live slots), got {max_len}")
        lg0, caches = self.prefill(params, tokens, max_len=max_len,
                                   mesh=mesh, prompt_lens=prompt_lens,
                                   page_table=page_table, n_pages=n_pages)
        tok0 = jnp.argmax(lg0[:, -1], -1).astype(jnp.int32)[:, None]
        pos0 = jnp.broadcast_to(jnp.reshape(jnp.asarray(
            prompt_lens if prompt_lens is not None else prompt_len,
            jnp.int32), (-1,)), (b,))
        limit = pos0 + gen_len - 1
        done0 = jnp.zeros((b,), bool) if stop_token is None else (
            tok0[:, 0] == stop_token)
        if stop_token is not None:
            tok0 = jnp.where(done0[:, None], stop_token, tok0)
        done0 = done0 | (pos0 >= limit)        # gen_len == 1: prefill only
        pad = stop_token if stop_token is not None else 0
        out0 = jnp.full((b, gen_len + k1), pad,
                        jnp.int32).at[:, 0].set(tok0[:, 0])
        rows = jnp.arange(b)[:, None]
        arange_k = jnp.arange(k1, dtype=jnp.int32)

        def cond(c):
            return ~jnp.all(c[6])

        def body(c):
            out, ec, tok, caches, pos, lens, done, rounds, emitted = c
            g, n, tok, pos, lens, done, caches = self.speculate_step(
                params, tok, caches, pos, lens=lens, done=done,
                limit=limit, spec_k=spec_k, draft_repeats=draft_repeats,
                stop_token=stop_token, mesh=mesh,
                draft_policy=draft_policy, _draft_fn=_draft_fn)
            valid = arange_k[None, :] < n[:, None]
            sidx = jnp.where(valid, ec[:, None] + arange_k[None, :],
                             gen_len + arange_k[None, :])
            out = out.at[rows, sidx].set(jnp.where(valid, g, pad))
            return (out, ec + n, tok, caches, pos, lens, done,
                    rounds + 1, emitted + jnp.sum(n))

        init = (out0, jnp.ones((b,), jnp.int32), tok0, caches, pos0,
                pos0, done0, jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32))
        fin = jax.lax.while_loop(cond, body, init)
        gen = fin[0][:, :gen_len]
        if return_stats:
            return gen, fin[7], fin[8]
        return gen

    def speculate_burst(self, params, tok, caches: Caches, pos, lens,
                        done, limit, *, spec_k: int, draft_repeats=None,
                        k_rows=None, max_len: int, out_width: int, n_max,
                        exit_on_finish, stop_token: Optional[int] = None,
                        key=None, mesh=None, guard: bool = False,
                        esc_fmts=None, kv_levels=None, poison_at=None,
                        ovf_at=None, ovf_scale=None, draft_policy=None,
                        _draft_fn=None):
        """Speculative twin of ``decode_burst``: up to ``n_max``
        ``speculate_step`` rounds as ONE compiled ``while_loop``, each
        emitting a VARIABLE number of tokens per row.  Unlike the plain
        burst's one-column-per-round layout, ``out[b]`` holds row b's
        accepted tokens PACKED contiguously — exactly ``new_lens[b] -
        old_lens[b]`` of them, so the engine's existing lens-growth
        accounting consumes the buffer unchanged.  The loop additionally
        exits when another full chunk might not fit ``out_width``.

        Greedy only (acceptance is defined against the verify argmax);
        the ``key`` passes through untouched for signature compatibility.
        ``k_rows`` [B] is the per-request draft cap (``0`` =
        ``no_speculate`` rows, which still verify their single next token
        — same batch, same compiled program, plain-decode results).
        Hooks mirror ``decode_burst``: ``poison_at``/``guard`` (NaN
        rounds + sanitize accounting), ``esc_fmts``/``kv_levels`` +
        ``ovf_at``/``ovf_scale`` (escalation writes; flags attribute the
        whole verify chunk to the row).  Returns ``(out [B, out_width],
        n_rounds, tok, caches, pos, lens, done, key[, bad][, kv_flags],
        stats [2])`` with ``stats = (live_row_rounds, emitted)``."""
        b = tok.shape[0]
        k1 = spec_k + 1
        done0 = done
        pad = stop_token if stop_token is not None else -1
        out0 = jnp.full((b, out_width + k1), pad, jnp.int32)
        n_max = jnp.asarray(n_max, jnp.int32)
        wave = jnp.asarray(exit_on_finish, jnp.int32)
        poison_at = (None if poison_at is None
                     else jnp.asarray(poison_at, jnp.int32))
        ovf_at = None if ovf_at is None else jnp.asarray(ovf_at, jnp.int32)
        esc = esc_fmts is not None
        rows = jnp.arange(b)[:, None]
        arange_k = jnp.arange(k1, dtype=jnp.int32)

        def cond(c):
            i, ec, done = c[0], c[2], c[7]
            more = (i < n_max) & ~jnp.all(done)
            newly = jnp.sum((done & ~done0).astype(jnp.int32))
            fits = jnp.max(jnp.where(done, 0, ec)) + k1 <= out_width
            return more & fits & ((wave == 0) | (newly < wave))

        def body(c):
            i, out, ec, tok, caches, pos, lens, done, stats = c[:9]
            extra = list(c[9:])
            badc = extra.pop(0) if guard else None
            flacc = extra.pop(0) if esc else None
            scale = (jnp.where(i == ovf_at, ovf_scale, 1.0)
                     if ovf_at is not None else None)
            r = self.speculate_step(
                params, tok, caches, pos, lens=lens, done=done,
                limit=limit, spec_k=spec_k, draft_repeats=draft_repeats,
                k_rows=k_rows, stop_token=stop_token, mesh=mesh,
                guard=guard, esc_fmts=esc_fmts, kv_levels=kv_levels,
                kv_scale=scale,
                poison=(i == poison_at) if poison_at is not None else None,
                draft_policy=draft_policy, _draft_fn=_draft_fn)
            g, n, tok, pos, new_lens, new_done, caches = r[:7]
            valid = arange_k[None, :] < n[:, None]
            sidx = jnp.where(valid, ec[:, None] + arange_k[None, :],
                             out_width + arange_k[None, :])
            out = out.at[rows, sidx].set(jnp.where(valid, g, pad))
            live = (~done).astype(jnp.int32)
            stats = stats + jnp.stack([jnp.sum(live), jnp.sum(n)])
            nc = (i + 1, out, ec + n, tok, caches, pos, new_lens,
                  new_done, stats)
            if guard:
                nc += (badc + (r[7] & ~done).astype(jnp.int32),)
            if esc:
                fl = r[7 + (1 if guard else 0)]
                nc += (flacc + fl * (~done).astype(jnp.int32)[:, None],)
            return nc

        init = (jnp.zeros((), jnp.int32), out0, jnp.zeros((b,), jnp.int32),
                tok, caches, pos, lens, done,
                jnp.zeros((2,), jnp.int32))
        if guard:
            init += (jnp.zeros((b,), jnp.int32),)
        if esc:
            init += (jnp.zeros((b, 2), jnp.int32),)
        fin = jax.lax.while_loop(cond, body, init)
        n, out, _, tok, caches, pos, lens, done, stats = fin[:9]
        extra = list(fin[9:])
        ret = (out[:, :out_width], n, tok, caches, pos, lens, done, key)
        if guard:
            ret += (extra.pop(0),)
        if esc:
            ret += (extra.pop(0),)
        return ret + (stats,)
