"""The grouped expert FFN (``kernels/grouped_ffn.py``) and the serving path
that runs it (``models/moe.py::moe_core_grouped``), in interpret mode.

Contract under test:

  * the kernel equals the ``kernels/ref.py`` oracle bitwise, and the
    whole grouped MoE layer equals ``naive_moe`` (tests/test_moe.py), for
    T = 1, 8 and 64 tokens (empty experts among them), all rows on one
    expert, fewer active experts than grid steps, and a layer index > 0
    into stacked weights;
  * each active expert's weights are fetched once: the weight block index
    changes exactly ``n_active`` times over the grid;
  * a row's output is bitwise the same alone and inside a batch;
  * served through the model, the grouped path keeps ``verify_chunk``
    bitwise equal to sequential decode, and ``decode_burst`` counts the
    experts read (all of them on the capacity einsum).

On the CPU ``moe.grouped_path`` is false (the kernel would interpret);
the model-level tests switch it on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import cached_model, small_batch
from repro.core.policy import get_policy
from repro.kernels import ops as kops
from repro.kernels.ref import grouped_ffn_ref
from repro.models import moe as moe_mod
from repro.models.moe import MoEConfig, moe_core_grouped, moe_params
from test_moe import naive_moe

D, F, E = 128, 256, 16

# (name, tokens, top_k, experts the router may pick, layers, layer)
CASES = [
    ("t1", 1, 2, None, 1, 0),
    ("t8", 8, 2, None, 1, 0),
    ("t64", 64, 4, None, 1, 0),
    ("one-expert", 24, 1, (5,), 1, 0),
    ("few-active", 8, 2, (1, 4, 6), 1, 0),
    ("layer2-of-3", 8, 2, None, 3, 2),
]


def _case(case):
    """Stacked layer weights, tokens and a router that sends every token
    to ``allowed`` experts only (x is positive, so a column of -1 loses)."""
    _, t, k, allowed, layers, layer = case
    cfg = MoEConfig(n_experts=E, top_k=k, d_expert=F)
    keys = jax.random.split(jax.random.key(7), layers + 1)
    per_layer = [moe_params(kk, D, cfg, jnp.bfloat16) for kk in keys[1:]]
    params = per_layer[layer]
    if allowed is not None:
        keep = jnp.zeros((E,), bool).at[jnp.asarray(allowed)].set(True)
        params["router"] = jnp.where(keep, params["router"], -1.0)
    stack = tuple(jnp.stack([p[n] for p in per_layer])
                  for n in ("w_gate", "w_up", "w_down"))
    x = jnp.abs(jax.random.normal(keys[0], (t, D))).astype(jnp.bfloat16)
    return cfg, params, stack, x, layer


def _sorted_rows(cfg, params, x):
    gates, idx, _ = moe_mod._route(x, params, cfg)
    order = jnp.argsort(idx.reshape(-1), stable=True)
    return x[order // cfg.top_k], idx.reshape(-1)[order]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_kernel_matches_oracle_and_fetches_each_expert_once(case):
    cfg, params, stack, x, layer = _case(case)
    rows, ids = _sorted_rows(cfg, params, x)
    y, n_active, fetched = kops.grouped_ffn(
        rows, ids, *stack, layer, policy="tp_bf16", interpret=True,
        debug_fetches=True)
    want = grouped_ffn_ref(rows, ids, *stack, layer)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    active = len(np.unique(np.asarray(ids)))
    assert int(n_active) == active
    assert int(np.asarray(fetched).sum()) == active
    if case[3] is not None:
        assert active <= len(case[3]) < len(fetched)   # visits to spare


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_grouped_layer_matches_naive_moe(case):
    cfg, params, stack, x, layer = _case(case)
    y, n_read = moe_core_grouped(x, params, stack + (layer,), cfg,
                                 get_policy("tp_bf16"))
    f32 = {n: v.astype(jnp.float32) for n, v in params.items()}
    want = np.asarray(naive_moe(x.astype(jnp.float32), f32, cfg, None))
    np.testing.assert_allclose(np.asarray(y, np.float32), want,
                               rtol=3e-2, atol=3e-2 * np.abs(want).max())
    _, idx, _ = moe_mod._route(x, params, cfg)
    assert int(n_read) == len(np.unique(np.asarray(idx)))


def test_row_output_does_not_depend_on_the_batch():
    cfg, params, stack, x, _ = _case(CASES[2])
    rows, ids = _sorted_rows(cfg, params, x)
    y, _ = kops.grouped_ffn(rows, ids, *stack, 0, policy="tp_bf16",
                            interpret=True)
    for r in (0, 17, rows.shape[0] - 1):
        alone, _ = kops.grouped_ffn(rows[r:r + 1], ids[r:r + 1], *stack, 0,
                                    policy="tp_bf16", interpret=True)
        np.testing.assert_array_equal(np.asarray(alone[0]),
                                      np.asarray(y[r]))


@pytest.fixture
def grouped(monkeypatch):
    """The serving path as a TPU takes it (the kernel interprets here)."""
    monkeypatch.setattr(moe_mod, "grouped_path", lambda *a: True)


def _moe_model():
    return cached_model("qwen3-moe-30b-a3b", paged_kv=True, page_size=16)


def test_grouped_verify_chunk_bitwise_matches_sequential_decode(grouped):
    model, params = _moe_model()
    toks, lens = small_batch(model.cfg.vocab)
    b = toks.shape[0]
    pre = jax.jit(lambda p, t, l: model.prefill(p, t, max_len=48,
                                                prompt_lens=l))
    lg0, c_seq = pre(params, toks, lens)
    _, c_chk = pre(params, toks, lens)
    tok = jnp.argmax(lg0[jnp.arange(b), lens - 1], -1).astype(
        jnp.int32)[:, None]
    step = jax.jit(lambda p, t, c, i: model.decode_step(
        p, t, c, i, kv_len=i + 1, serving=True))
    chunk, seq_lg, pos = [tok], [], jnp.asarray(lens)
    for i in range(4):
        lg, c_seq, n_read = step(params, chunk[-1], c_seq, pos + i)
        assert 0 < int(n_read) <= model.cfg.n_layers * b * model.cfg.moe.top_k
        seq_lg.append(lg[:, -1])
        chunk.append(jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None])
    offs = pos[:, None] + jnp.arange(4, dtype=jnp.int32)
    v_lg, c_chk = jax.jit(lambda p, t, c, i, kl: model.verify_chunk(
        p, t, c, i, kv_len=kl))(params, jnp.concatenate(chunk[:4], 1),
                                c_chk, pos, offs + 1)
    np.testing.assert_array_equal(
        np.stack([np.asarray(x, np.float32) for x in seq_lg], 1),
        np.asarray(v_lg, np.float32))


@pytest.mark.parametrize("path", ["grouped", "einsum"])
def test_burst_counts_the_experts_read(path, request):
    if path == "grouped":
        request.getfixturevalue("grouped")
    model, params = _moe_model()
    cfg = model.cfg
    toks, lens = small_batch(cfg.vocab)
    b = toks.shape[0]
    lg0, caches = jax.jit(lambda p, t, l: model.prefill(
        p, t, max_len=48, prompt_lens=l))(params, toks, lens)
    tok = jnp.argmax(lg0[jnp.arange(b), lens - 1], -1).astype(
        jnp.int32)[:, None]
    rounds = 5
    r = jax.jit(lambda p, t, c, pos: model.decode_burst(
        p, t, c, pos, pos, jnp.zeros((b,), bool), pos + 40, max_len=48,
        out_width=8, n_max=rounds, exit_on_finish=0))(params, tok, caches,
                                                      lens)
    n_read, every = int(r[-1]), rounds * cfg.n_layers * cfg.moe.n_experts
    assert int(r[1]) == rounds
    if path == "einsum":
        assert n_read == every
    else:
        assert 0 < n_read <= rounds * cfg.n_layers * min(
            cfg.moe.n_experts, b * cfg.moe.top_k)
