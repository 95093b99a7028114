"""Continuous-batching serving engine, end to end.

Contract under test (the serving-loop analogue of the ragged/paged PRs):

  * the serving while_loop — ``Model.decode_burst``, started from the
    same prefilled caches, emits exactly the tokens of ``generate``'s
    scan, leaves after the round every row hit the stop token (strictly
    below its cap), and composes with EOS + sampling (the key split as
    ``generate`` splits it) + penalties in one carry.
  * penalties — repetition/presence penalties key off a prompt+emitted
    count histogram, apply before temperature/top-k/top-p, and leave the
    default greedy graph bit-identical.
  * chunked prefill — ``Model.prefill_chunk`` through the paged read
    path reconstructs full-prefill logits exactly and a decode started
    from chunked caches emits the tokens full prefill would.
  * engine — requests served in a shared continuous batch emit exactly
    the tokens they'd get served alone (greedy); same queue -> same
    tokens; pages drain back to the allocator (scratch only) with a
    high-water mark below the fixed-batch equivalent; admission, page
    churn and EOS never retrace the single compiled burst program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import LENS, cached_model, small_batch
from repro.launch.engine import ContinuousEngine, Request, synthetic_trace
from repro.models.paged import PageAllocator
from repro.models.registry import build_model
from repro.models.transformer import (_bump_counts, apply_penalties,
                                      init_caches, token_counts)


def _setup(policy="tp_bf16", **cfg):
    model, params = cached_model("gemma2-9b", policy=policy, **cfg)
    toks, lens = small_batch(model.cfg.vocab)
    return model, params, toks, lens


# ---------------------------------------------------------------------------
# decode_burst (the engine's while_loop) against generate's scan
# ---------------------------------------------------------------------------
def _scan_and_burst(model, params, toks, *, gen_len, max_len, lens=None,
                    stop_token=None, key=None, **sampling):
    """``generate`` (scan) and ``Model.decode_burst`` from one prefill.

    The burst picks up where the scan's first token left off: the same
    prefilled caches, the scan's token 0 (EOS-frozen), its count
    histogram when penalties are on, and the key after ``generate``'s
    first split.  Returns ``(scan [B, gen_len], burst [B, gen_len],
    n_steps)``."""
    b, width = toks.shape
    key = jax.random.key(0) if key is None else key
    pen = (sampling.get("repetition_penalty") is not None
           or sampling.get("presence_penalty") is not None)

    def run(p, t, k):
        gen = model.generate(p, t, gen_len=gen_len, max_len=max_len,
                             prompt_lens=lens, stop_token=stop_token, key=k,
                             **sampling)[0]
        _, caches = model.prefill(p, t, max_len=max_len, prompt_lens=lens)
        pos = (jnp.full((b,), width, jnp.int32) if lens is None
               else jnp.asarray(lens, jnp.int32))
        tok0 = gen[:, :1]
        done = (tok0[:, 0] == stop_token if stop_token is not None
                else jnp.zeros((b,), bool))
        counts = (_bump_counts(token_counts(t, model.vocab_out, lens), tok0)
                  if pen else None)
        out, n = model.decode_burst(
            p, tok0, caches, pos, pos, done, pos + gen_len - 1,
            max_len=max_len, out_width=gen_len - 1, n_max=gen_len - 1,
            exit_on_finish=0, stop_token=stop_token,
            key=jax.random.split(k)[0], counts=counts, **sampling)[:2]
        return gen, jnp.concatenate([tok0, out], axis=1), n

    gen, burst, n = jax.jit(run)(params, toks, key)
    return np.asarray(gen), np.asarray(burst), int(n)


def test_burst_matches_scan_greedy_tokens():
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    toks, _ = small_batch(model.cfg.vocab)
    g_s, g_b, n = _scan_and_burst(model, params, toks, gen_len=6,
                                  max_len=48)
    # no stop token: the burst runs every round up to its cap
    assert n == 5
    np.testing.assert_array_equal(g_s, g_b)


def _row_one_stop(model, params, toks, **kw):
    """A stop token that row 1 emits mid-stream and rows 0 and 2 never
    do, read off a run without one (a stop changes nothing before it
    fires), so the rows finish at different rounds."""
    free = _scan_and_burst(model, params, toks, **kw)[0]
    stop = int(free[1, 2])
    assert stop not in free[0] and stop not in free[2], free
    return stop


def test_burst_matches_scan_with_eos_ragged_sampling():
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    toks, lens = small_batch(model.cfg.vocab)
    kw = dict(gen_len=8, max_len=48, lens=lens, temperature=0.9, top_k=40,
              key=jax.random.key(7))
    stop = _row_one_stop(model, params, toks, **kw)
    g_s, g_b, n = _scan_and_burst(model, params, toks, stop_token=stop,
                                  **kw)
    np.testing.assert_array_equal(g_s, g_b)
    assert (g_b[1, 2:] == stop).all() and n == 7


def test_burst_early_exit_round_count():
    """All rows hitting EOS at round k must leave the burst after k rounds
    — strictly below its cap — with tokens bit-identical to the scan,
    whose frozen tail the burst's stop-token pad reproduces.  (A crushing
    repetition penalty makes the greedy rollout all-distinct, so any
    mid-run token is a stop that first fires exactly there.)"""
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    toks, _ = small_batch(model.cfg.vocab)
    toks = jnp.broadcast_to(toks[0:1], (3, 32))         # identical rows
    g0 = np.asarray(jax.jit(lambda p, t: model.generate(
        p, t, gen_len=10, max_len=48, repetition_penalty=1e9)[0])(params,
                                                                  toks))
    k = 5
    assert g0[0, k] not in g0[0, :k]                    # all-distinct row
    stop = int(g0[0, k])
    g_s, g_b, n = _scan_and_burst(model, params, toks, gen_len=10,
                                  max_len=48, stop_token=stop,
                                  repetition_penalty=1e9)
    np.testing.assert_array_equal(g_s, g_b)
    assert n == k < 9, (n, k)
    assert (g_b[:, k:] == stop).all()


# ---------------------------------------------------------------------------
# repetition / presence penalties
# ---------------------------------------------------------------------------
def test_apply_penalties_semantics():
    lg = jnp.asarray([[2.0, -2.0, 1.0, 0.5]])
    counts = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    out = np.asarray(apply_penalties(lg, counts, repetition_penalty=2.0))
    # seen positive logit divided, seen negative multiplied, unseen intact
    np.testing.assert_allclose(out, [[1.0, -4.0, 1.0, 0.5]])
    out = np.asarray(apply_penalties(lg, counts, presence_penalty=0.75))
    np.testing.assert_allclose(out, [[1.25, -2.75, 1.0, 0.5]])
    # neutral knobs are the identity
    np.testing.assert_array_equal(
        np.asarray(apply_penalties(lg, counts, repetition_penalty=1.0,
                                   presence_penalty=0.0)), np.asarray(lg))


def test_token_counts_masks_ragged_pad():
    toks = jnp.asarray([[5, 6, 5, 0], [7, 0, 0, 0]], jnp.int32)
    cnt = np.asarray(token_counts(toks, 10, jnp.asarray([3, 1], jnp.int32)))
    assert cnt[0, 5] == 2 and cnt[0, 6] == 1 and cnt[0, 0] == 0
    assert cnt[1, 7] == 1 and cnt[1].sum() == 1


def test_penalties_default_is_bit_identical_and_active_differs():
    model, params, toks, _ = _setup()
    g0 = np.asarray(jax.jit(lambda p, t: model.generate(
        p, t, gen_len=6, max_len=40)[0])(params, toks))
    g_neutral = np.asarray(jax.jit(lambda p, t: model.generate(
        p, t, gen_len=6, max_len=40, repetition_penalty=1.0,
        presence_penalty=0.0)[0])(params, toks))
    np.testing.assert_array_equal(g0, g_neutral)
    # a crushing repetition penalty forbids re-emitting ANY seen token:
    # within the generated window every token is then unique per row
    g_r = np.asarray(jax.jit(lambda p, t: model.generate(
        p, t, gen_len=6, max_len=40, repetition_penalty=1e9)[0])(params,
                                                                toks))
    for b in range(g_r.shape[0]):
        assert len(set(g_r[b].tolist())) == g_r.shape[1], g_r[b]


def test_penalties_compose_with_sampling_eos_and_burst():
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    toks, lens = small_batch(model.cfg.vocab)
    kw = dict(gen_len=6, max_len=48, lens=lens, temperature=0.8, top_k=50,
              key=jax.random.key(9), repetition_penalty=1.3,
              presence_penalty=0.2)
    stop = _row_one_stop(model, params, toks, **kw)
    s1, b1, _ = _scan_and_burst(model, params, toks, stop_token=stop, **kw)
    s2, _, _ = _scan_and_burst(model, params, toks, stop_token=stop, **kw)
    np.testing.assert_array_equal(s1, s2)          # key-deterministic
    np.testing.assert_array_equal(s1, b1)          # burst == scan
    assert (b1[1, 2:] == stop).all()


# ---------------------------------------------------------------------------
# chunked prefill through the paged read path
# ---------------------------------------------------------------------------
def _chunked_prefill(model, params, toks, lens, *, max_len, chunk):
    caches = init_caches(model.cfg, toks.shape[0], max_len, model.policy)
    lg = None
    for off in range(0, toks.shape[1], chunk):
        cl = jnp.clip(lens - off, 0, chunk)
        lg_c, caches = model.prefill_chunk(params, toks[:, off:off + chunk],
                                           caches, q_offset=off,
                                           chunk_lens=cl)
        lg = lg_c if lg is None else jnp.where((cl > 0)[:, None, None],
                                               lg_c, lg)
    return lg, caches


@pytest.mark.parametrize("chunk", [8, 16])
def test_prefill_chunk_matches_full_paged_prefill(chunk):
    """Chunk boundaries must be invisible: same last-live logits BITWISE,
    and greedy decode from chunked caches emits the tokens a full paged
    prefill + generate would."""
    model, params, toks, lens = _setup(paged_kv=True, page_size=16)
    lg_f, _ = jax.jit(lambda p, t, l: model.prefill(
        p, t, max_len=48, prompt_lens=l))(params, toks, lens)
    lg_c, caches = jax.jit(lambda p, t, l: _chunked_prefill(
        model, p, t, l, max_len=48, chunk=chunk))(params, toks, lens)
    np.testing.assert_array_equal(np.asarray(lg_f), np.asarray(lg_c))

    gen_ref = np.asarray(jax.jit(lambda p, t, l: model.generate(
        p, t, gen_len=5, max_len=48, prompt_lens=l)[0])(params, toks, lens))

    def roll(p, c, l):
        tok = jnp.argmax(lg_c[:, -1], -1).astype(jnp.int32)[:, None]
        outs, pos = [tok], l
        for _ in range(4):
            lg, c = model.decode_step(p, outs[-1], c, pos)
            outs.append(jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None])
            pos = pos + 1
        return jnp.concatenate(outs, 1)

    got = np.asarray(jax.jit(roll)(params, caches, lens))
    np.testing.assert_array_equal(gen_ref, got)


def test_prefill_chunk_row_subset_matches_batch():
    """A single-slot (traced row index) chunk writes exactly what the
    full-batch chunk writes for that row — the admission code path."""
    model, params, toks, lens = _setup(paged_kv=True, page_size=16)
    _, c_batch = jax.jit(lambda p, t, l: _chunked_prefill(
        model, p, t, l, max_len=48, chunk=16))(params, toks, lens)

    def rowwise(p, t, l):
        caches = init_caches(model.cfg, t.shape[0], 48, model.policy)
        for b in range(t.shape[0]):
            for off in range(0, t.shape[1], 16):
                cl = jnp.clip(l[b:b + 1] - off, 0, 16)
                _, caches = model.prefill_chunk(
                    p, t[b:b + 1, off:off + 16], caches, q_offset=off,
                    row=jnp.asarray(b, jnp.int32), chunk_lens=cl)
        return caches

    c_rows = jax.jit(rowwise)(params, toks, lens)
    for a, b in zip(jax.tree.leaves(c_batch), jax.tree.leaves(c_rows)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefill_chunk_requires_paged():
    model, params, toks, lens = _setup()                 # contiguous
    caches = init_caches(model.cfg, 3, 48, model.policy)
    with pytest.raises(ValueError, match="paged"):
        model.prefill_chunk(params, toks[:, :16], caches, q_offset=0)


def test_paged_prefill_pallas_reads_pool_matches_dense():
    """The satellite gate: under cfg.paged_kv Model.prefill routes reads
    through the paged flash path (block_table in the kernel's index maps);
    it must match the dense gather fallback at the usual model parity
    tolerance, and the gather fallback itself is bit-identical to the
    contiguous model (covered by test_paged_attention)."""
    model, params, toks, lens = _setup(paged_kv=True, page_size=16)
    lg_d, _ = jax.jit(lambda p, t, l: model.prefill(
        p, t, max_len=48, prompt_lens=l))(params, toks, lens)
    mp = model.with_cfg(prefill_backend="pallas")
    lg_p, _ = jax.jit(lambda p, t, l: mp.prefill(
        p, t, max_len=48, prompt_lens=l))(params, toks, lens)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d),
                               rtol=5e-2, atol=1e-1)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
def _mk_requests(vocab, seed=0):
    rng = np.random.RandomState(seed)
    lens = (8, 20, 32, 13, 27, 5, 32, 16)
    budgets = (4, 9, 3, 7, 5, 8, 2, 6)
    arrivals = (0, 0, 0, 0, 2, 2, 5, 9)
    return [Request(rid=i, tokens=rng.randint(0, vocab, size=L).tolist(),
                    max_new=B, arrival=A)
            for i, (L, B, A) in enumerate(zip(lens, budgets, arrivals))]


@pytest.fixture(scope="module")
def engine_run():
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    reqs = _mk_requests(model.cfg.vocab)
    eng = ContinuousEngine(model, params, slots=3, max_len=48, chunk=16)
    fin1, stats1 = eng.run(reqs)
    fin2, stats2 = eng.run(reqs)       # same engine, same queue, again
    return model, params, reqs, eng, (fin1, stats1), (fin2, stats2)


def test_engine_matches_solo_generate(engine_run):
    """Every request served in the shared continuous batch (3 slots, 8
    requests, multi-chunk prompts, mid-generation admission) emits
    exactly the tokens it would get served ALONE through generate()."""
    model, params, reqs, _, (fin, _), _ = engine_run
    gen = jax.jit(lambda p, t, g: model.generate(
        p, t, gen_len=g, max_len=48)[0], static_argnums=2)
    for r, f in zip(reqs, fin):
        want = np.asarray(gen(params, jnp.asarray(r.tokens, jnp.int32)[None],
                              r.max_new))[0].tolist()
        assert f.tokens == want, (r.rid, f.tokens, want)
        assert len(f.tokens) == r.max_new


def test_engine_admission_determinism(engine_run):
    """Same queue -> same tokens, same rounds, same page watermark."""
    _, _, _, _, (fin1, st1), (fin2, st2) = engine_run
    for a, b in zip(fin1, fin2):
        assert a.tokens == b.tokens and a.finish_round == b.finish_round
    assert st1["rounds"] == st2["rounds"]
    assert st1["peak_live_pages"] == st2["peak_live_pages"]


def test_engine_page_accounting(engine_run):
    """Pages recycle: after the run only the scratch page is live, and
    the high-water mark stayed below the fixed-batch equivalent (lazy
    allocation tracks live lengths, not slots x max_len)."""
    _, _, reqs, eng, (fin, stats), _ = engine_run
    assert eng.alloc.n_live == 1                       # scratch only
    # reported stats exclude the always-live scratch page
    assert eng.alloc.stats()["peak_live"] == stats["peak_live_pages"] + 1
    assert 1 < stats["peak_live_pages"] < stats["fixed_equiv_pages"]
    # admission interleaves with decode: some request finished before the
    # last one was even admitted (mid-generation page recycling)
    first_fin = min(f.finish_round for f in fin)
    last_admit = max(f.admit_round for f in fin)
    assert first_fin <= last_admit


def test_engine_no_retrace_across_admissions(engine_run):
    """Admission, EOS churn, table swaps: ONE compiled burst program for
    the whole run (state and tables are traced), and chunk programs only
    per static (offset, wave-width) pair."""
    _, _, _, eng, _, _ = engine_run
    assert eng._burst._cache_size() == 1
    assert all(fn._cache_size() == 1 for fn in eng._chunk_fns.values())


def test_engine_stop_token_frees_early():
    """A stop token cuts a row's generation below its budget and the
    tokens match solo generate's EOS semantics (stop kept, then freeze)."""
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    # the rid-4 prompt's greedy rollout changes token mid-run (probed):
    # its first divergent token is a stop that fires mid-decode
    probe = _mk_requests(model.cfg.vocab)[4]
    g = np.asarray(jax.jit(lambda p, t: model.generate(
        p, t, gen_len=9, max_len=48)[0])(
            params, jnp.asarray(probe.tokens, jnp.int32)[None]))[0]
    k = next((i for i in range(1, 9) if g[i] != g[0]), None)
    if k is None:
        pytest.skip("constant greedy rollout; no mid-run stop available")
    stop = int(g[k])
    reqs = [Request(rid=0, tokens=probe.tokens, max_new=9),
            Request(rid=1, tokens=probe.tokens[:5], max_new=4)]
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16,
                           stop_token=stop)
    fin, _ = eng.run(reqs)
    f0 = fin[0]
    assert f0.tokens == g[:k + 1].tolist()             # ends at the stop
    assert len(f0.tokens) == k + 1 <= 9
    assert eng.alloc.n_live == 1


def test_engine_penalties_match_solo_generate(engine_run):
    """Repetition/presence penalties threaded through the continuous
    path (count histograms seeded at admission, bumped inside the burst
    carry, re-seeded across bursts) emit exactly what solo generate's
    penalty carry produces — the --continuous flags behave like the solo
    ones."""
    model, params, reqs, _, _, _ = engine_run
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16,
                           repetition_penalty=1.3, presence_penalty=0.4)
    sub = reqs[:5]
    fin, _ = eng.run(sub)
    gen = jax.jit(lambda p, t, g: model.generate(
        p, t, gen_len=g, max_len=48, repetition_penalty=1.3,
        presence_penalty=0.4)[0], static_argnums=2)
    for r, f in zip(sub, fin):
        want = np.asarray(gen(params, jnp.asarray(r.tokens, jnp.int32)[None],
                              r.max_new))[0].tolist()
        assert f.tokens == want, (r.rid, f.tokens, want)
    assert eng._burst._cache_size() == 1       # penalties don't retrace


def test_engine_refuses_unpageable_and_unpaged():
    model, params = cached_model("gemma2-9b")
    with pytest.raises(ValueError, match="paged_kv"):
        ContinuousEngine(model, params, slots=2, max_len=32)
    zamba = build_model("zamba2-1.2b", policy="tp_bf16",
                        reduced=True).with_cfg(paged_kv=True)
    with pytest.raises(ValueError, match="cannot page"):
        ContinuousEngine(zamba, zamba.init(jax.random.key(0)), slots=2,
                         max_len=32)


def test_engine_oversized_request_rejected():
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    eng = ContinuousEngine(model, params, slots=2, max_len=32, chunk=16)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.run([Request(rid=0, tokens=[1] * 30, max_new=8)])


def test_synthetic_trace_deterministic():
    a = synthetic_trace(12, 4, 32, 64, 256)
    b = synthetic_trace(12, 4, 32, 64, 256)
    assert [(r.tokens, r.max_new, r.arrival) for r in a] == \
        [(r.tokens, r.max_new, r.arrival) for r in b]
    assert any(r.max_new == 64 for r in a) and any(r.arrival > 0 for r in a)


# ---------------------------------------------------------------------------
# allocator hooks
# ---------------------------------------------------------------------------
def test_allocator_peak_and_probe():
    a = PageAllocator(4)
    assert a.try_alloc(5) is None and a.n_live == 0     # probe, no effect
    ids = a.alloc(3)
    assert a.peak_live == 3
    a.free(ids)
    assert a.n_live == 0 and a.peak_live == 3           # watermark sticks
    a.reset_peak()
    assert a.peak_live == 0
    got = a.try_alloc(2)
    assert got is not None and a.peak_live == 2
    assert a.stats() == {"n_pages": 4, "n_live": 2, "n_free": 2,
                         "peak_live": 2}
