"""Per-architecture smoke tests: for every assigned arch, instantiate the
REDUCED same-family config and run (a) one forward/train step and (b) a
prefill + two decode steps, on CPU, asserting output shapes, finiteness and
cache consistency.  The FULL configs are exercised only via the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import ARCHS, build_model, get_config
from repro.models import transformer as tfm

BATCH, SEQ = 2, 32


def _inputs(model, key):
    cfg = model.cfg
    ks = jax.random.split(key, 3)
    tokens = jax.random.randint(ks[0], (BATCH, SEQ), 0, cfg.vocab)
    labels = jax.random.randint(ks[1], (BATCH, SEQ), 0, cfg.vocab)
    fe = None
    if cfg.frontend == "patch":
        fe = jax.random.normal(
            ks[2], (BATCH, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    elif cfg.frontend == "audio":
        fe = jax.random.normal(
            ks[2], (BATCH, cfg.encoder.n_frames, cfg.d_model), jnp.float32)
    return tokens, labels, fe


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    model = build_model(arch, policy="tp_bf16", reduced=True)
    params = model.init(jax.random.key(0))
    tokens, labels, fe = _inputs(model, jax.random.key(1))

    def loss_fn(p):
        return model.forward_train(p, tokens, labels, frontend_embeds=fe,
                                   remat=True)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss)), arch
    # a sensible initial LM loss is ~log(vocab)
    assert 0.5 * np.log(model.cfg.vocab) < float(loss) < 3 * np.log(
        model.cfg.vocab), (arch, float(loss))
    gnorm = jax.tree.reduce(
        lambda a, g: a + jnp.sum(jnp.abs(g.astype(jnp.float32))), grads, 0.0)
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, arch


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_smoke(arch):
    model = build_model(arch, policy="tp_bf16", reduced=True)
    cfg = model.cfg
    params = model.init(jax.random.key(0))
    tokens, _, fe = _inputs(model, jax.random.key(1))
    max_len = SEQ + 8

    lg, caches = jax.jit(
        lambda p, t: model.prefill(p, t, max_len=max_len,
                                   frontend_embeds=fe))(params, tokens)
    assert lg.shape == (BATCH, 1, model.vocab_out)
    assert np.all(np.isfinite(np.asarray(lg, np.float32))), arch

    step = jax.jit(lambda p, t, c, pos: model.decode_step(p, t, c, pos))
    tok = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for i in range(2):
        lg2, caches = step(params, tok, caches, SEQ + i)
        assert lg2.shape == (BATCH, 1, model.vocab_out)
        assert np.all(np.isfinite(np.asarray(lg2, np.float32))), arch
        tok = jnp.argmax(lg2[:, -1], axis=-1).astype(jnp.int32)[:, None]


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_validates(arch):
    cfg = get_config(arch)
    cfg.validate()
    counts = cfg.param_counts()
    assert counts["total"] > 0 and counts["active"] > 0
    assert counts["active"] <= counts["total"]
    assert counts["flops"] >= counts["active"]
    assert len(cfg.layer_list()) == cfg.n_layers


def test_decode_matches_prefill_continuation():
    """Decoding token-by-token must equal prefilling the longer prompt
    (KV-cache correctness, the core serving invariant)."""
    model = build_model("granite-20b", policy="fp32", reduced=True)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 12), 0, model.cfg.vocab)
    max_len = 16

    lg_a, caches = model.prefill(params, toks[:, :8], max_len=max_len)
    for i in range(4):
        lg_a, caches = model.decode_step(params, toks[:, 8 + i:9 + i],
                                         caches, 8 + i)
    lg_b, _ = model.prefill(params, toks, max_len=max_len)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                               rtol=2e-4, atol=2e-4)


def test_param_counts_against_public_sizes():
    """Sanity-check the config dims against the models' public parameter
    counts (loose bands — our configs are backbone-only)."""
    bands = {
        "gemma2-9b": (8e9, 11e9),
        "gemma3-12b": (10e9, 14e9),
        "granite-20b": (18e9, 22e9),
        "minicpm3-4b": (3.5e9, 5e9),
        # assignment dims (proj_factor 2, headwise qkv) give ~1.9e9;
        # the public 1.3B uses narrower internals — recorded in DESIGN.md
        "xlstm-1.3b": (1.4e9, 2.2e9),
        "zamba2-1.2b": (1.0e9, 1.6e9),
        "qwen3-moe-30b-a3b": (28e9, 32e9),
        "deepseek-v2-lite-16b": (14e9, 17e9),
        "internvl2-26b": (18e9, 22e9),   # LLM backbone of the 26B (ViT stub)
        "internlm2-20b": (19e9, 21e9),
        "whisper-small": (0.2e9, 0.3e9),
    }
    for arch, (lo, hi) in bands.items():
        n = get_config(arch).param_counts()["total"]
        assert lo <= n <= hi, (arch, f"{n:.2e}", lo, hi)
