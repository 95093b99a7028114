"""internlm2-20b served through the paged pool against the plain reference.

At the reduced size (2 layers, d 64, 4/2 heads of 16, SwiGLU 128) on
seeded random weights (``bench/weights.py``), a 40-token prompt is
prefilled in 16-token chunks through ``prefill_chunk`` over a paged pool,
so the chunks at ``q_offset`` 16 and 32 read the earlier ones back through
the block table with the Pallas flash kernel; then ``decode_round`` (the
burst's round) decodes 8 tokens through the pool with the Pallas decode
kernel.  Every position's logits are held to the float32 reference's full
forward pass over the prompt and the served tokens
(``bench/check/reference.py``, which imports nothing of the program).

Each chunk is served twice: first as a probe wave of ``CHUNK`` rows whose
live lengths are 1..CHUNK, whose last-position logits are therefore the
logits of every position in the chunk, then whole, to leave the chunk's
K/V in the pool for the next.  ``ContinuousEngine`` serves the same
prompt, and every token it emits is the reference's argmax.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.check import reference
from repro.launch.engine import ContinuousEngine, Request
from repro.models.registry import build_model
from repro.models.transformer import init_caches

ARCH, SEED = "internlm2-20b", 1501
CHUNK, PROMPT, DECODE, PAGE, MAX_LEN = 16, 40, 8, 16, 64
#: float32 program against the float32 reference: the same equations in
#: float32, summed in another order (online softmax over pool pages,
#: blocked matmuls), so logits of order 1 agree to a few float32 steps
#: (4e-6 at this seed)
FP32_ATOL = 1e-4
#: bf16 program against the float32 reference, which reads the same bf16
#: weights: bf16 activations and K/V (relative step 2^-8) through 2
#: layers move logits of order 1 by up to 0.073 at this seed; the float8
#: control (relative step 2^-4) moves them by 0.19 to 1.07
BF16_ATOL = 0.12


def _model(policy):
    return build_model(ARCH, policy=policy, reduced=True).with_cfg(
        paged_kv=True, page_size=PAGE, decode_backend="pallas",
        prefill_backend="pallas")


def _spec(model):
    c = model.cfg
    return reference.Spec(
        layers=c.n_layers, d=c.d_model, heads=c.n_heads,
        kv_heads=c.n_kv_heads, head_dim=c.head_dim, vocab=c.vocab,
        eps=c.norm_eps, theta=c.rope_theta, qk_norm=False, experts=0,
        top_k=0, norm_topk=False)


def _prompt(vocab):
    return np.random.default_rng(SEED).integers(0, vocab, PROMPT).tolist()


@functools.lru_cache(maxsize=None)
def _served(policy):
    """(prompt + served tokens, program logits [PROMPT + DECODE, V],
    params): every prompt position's logits from the chunked prefill,
    then each decoded position's."""
    model = _model(policy)
    params = weights.make(model, SEED)
    prompt = _prompt(model.cfg.vocab)
    caches = init_caches(model.cfg, CHUNK, MAX_LEN, model.policy)
    chunk = jax.jit(lambda p, t, c, lens, off: model.prefill_chunk(
        p, t, c, q_offset=off, chunk_lens=lens), static_argnums=4)
    rows = []
    for off in range(0, PROMPT, CHUNK):
        piece = prompt[off:off + CHUNK]
        t = np.zeros((CHUNK, CHUNK), np.int32)
        t[:, :len(piece)] = piece
        t = jnp.asarray(t)
        probe = np.minimum(np.arange(1, CHUNK + 1), len(piece))
        lg, _ = chunk(params, t, caches, jnp.asarray(probe, jnp.int32), off)
        rows.append(np.asarray(lg)[:len(piece), 0])
        _, caches = chunk(params, t, caches,
                          jnp.full((CHUNK,), len(piece), jnp.int32), off)
    step = jax.jit(lambda p, tok, c, pos: model.decode_round(
        p, tok, c, pos, lens=pos, done=jnp.zeros(pos.shape, bool))[:3])
    tok = np.full((CHUNK, 1), np.argmax(rows[-1][-1]), np.int32)
    served = [int(tok[0, 0])]
    for i in range(DECODE):
        pos = jnp.full((CHUNK,), PROMPT + i, jnp.int32)
        nxt, lg, caches = step(params, jnp.asarray(tok), caches, pos)
        rows.append(np.asarray(lg)[:1, -1])
        tok = np.asarray(nxt)
        served.append(int(tok[0, 0]))
    return prompt + served[:-1], np.concatenate(rows), params


def _reference_logits(params, spec, tokens, control=False):
    x = reference.hidden(params, spec, tokens, control=control)
    h = reference._rms(x[:len(tokens)], params["norm_f"]["g"], spec.eps)
    lg = reference._mm("nd,dv->nv", h, params["lm_head"], control)
    return np.asarray(lg)[:, :spec.vocab]


@pytest.mark.parametrize("policy,atol", [("fp32", FP32_ATOL),
                                         ("tp_bf16", BF16_ATOL)])
def test_chunked_prefill_and_decode_match_the_reference(policy, atol):
    tokens, got, params = _served(policy)
    assert len(tokens) == got.shape[0] == PROMPT + DECODE
    spec = _spec(_model(policy))
    ref = _reference_logits(params, spec, tokens)
    err = np.abs(got[:, :spec.vocab] - ref).max(axis=1)
    assert err.max() <= atol, (policy, err.max(), np.argmax(err))
    # logits large enough that the tolerance does not hold by default
    assert np.abs(ref).max() > 10 * atol


def test_the_float8_control_fails_the_bf16_tolerance():
    tokens, _, params = _served("tp_bf16")
    spec = _spec(_model("tp_bf16"))
    ref = _reference_logits(params, spec, tokens)
    ctrl = _reference_logits(params, spec, tokens, control=True)
    assert np.abs(ctrl - ref).max() > BF16_ATOL


def test_the_engine_serves_the_references_argmax():
    model = _model("fp32")
    params = weights.make(model, SEED)
    prompt = _prompt(model.cfg.vocab)
    eng = ContinuousEngine(model, params, slots=2, max_len=MAX_LEN,
                           chunk=CHUNK, burst_cap=4)
    out, stats = eng.run([Request(rid=0, tokens=prompt, max_new=DECODE + 1)])
    served = list(out[0].tokens)
    assert len(served) == DECODE + 1 and stats["bursts"] >= 2
    gap = reference.score(params, _spec(model), prompt, served)["gap"]
    assert np.all(np.asarray(gap) <= FP32_ATOL), gap


def test_internvl2_takes_its_backbone_from_internlm2():
    """internvl2-26b's language model is InternLM2-20B's: every width and
    constant comes from ``internlm2_20b`` (rms_norm_eps 1e-5 among them,
    where the base default is 1e-6); only the vocabulary (92,553) and the
    patch frontend are the VLM's own."""
    from repro.configs import internlm2_20b, internvl2_26b
    lm, vl = internlm2_20b.CONFIG, internvl2_26b.CONFIG
    assert vl.norm_eps == 1e-5 and vl.rope_theta == 1e6
    assert (vl.vocab, vl.frontend, vl.family) == (92553, "patch", "vlm")
    for f in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "pattern", "norm_eps", "rope_theta", "tie_embeddings"):
        assert getattr(vl, f) == getattr(lm, f), f
