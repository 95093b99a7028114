"""Numerical-health telemetry: IEEE exception flags through the stack.

Contract under test (the FPnew §II.B ``fflags`` story, carried from the
cast emulation layer up to the serving scheduler):

  * cast flags — ``softfloat.quantize_with_flags`` returns per-element
    OF/UF/NX/NV masks that match an independent ml_dtypes-derived oracle
    on an exhaustive 16-bit sweep, in BOTH cast modes (IEEE overflow to
    ±Inf, and saturating: clamp to ±max-normal — same flags, different
    value).  ``quant_common``'s bit-twiddling twin agrees bitwise.
  * kernel flags — the Pallas decode / flash kernels accumulate per-row
    flag counters (``debug_flags``) that equal the schedule-aware ref.py
    oracles under ragged ``kv_lens`` and scrambled paged block tables:
    per-row EXACT, dead/padded slots contribute zero, and turning the
    telemetry on leaves the attention output bit-identical.
  * write-path ladder — ``models.attention.quantize_kv_rows`` snaps K/V
    writes to each row's escalation rung (saturating) and reports per-row
    OF/UF pressure.
  * engine escalation — overflow-injected requests under an
    ``EscalationPolicy`` finish their full budget at a wider KV rung with
    ZERO poisoned rounds (saturation keeps logits finite while pressure
    accumulates); escalation is refusable, deferrable under page
    pressure, and replay-deterministic.
  * SDC-checked swap — a bit-flipped swap payload is detected by the
    swap-in checksum, recovered by re-ingest, and the recovered request's
    tokens are bit-identical to an uncorrupted run.
  * restart hygiene — ``run_with_restarts`` gives every attempt fresh
    watchdog / straggler state.
"""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.core import softfloat
from repro.core.formats import get_format
from repro.core.policy import EscalationPolicy, get_policy
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant_common import (quantize_flag_masks,
                                        quantize_rne_bits)
from repro.launch.engine import ContinuousEngine, Request
from repro.models.attention import quantize_kv_rows
from repro.train.fault import (ServeFaultPlan, ServeWatchdog,
                               SimulatedFailure, StragglerMonitor,
                               run_with_restarts)

F32 = np.float32

NATIVE = [("fp8", ml_dtypes.float8_e5m2),
          ("fp16", np.float16),
          ("fp16alt", ml_dtypes.bfloat16)]


def _sweep16():
    """Every f32 value reachable from a 16-bit pattern: all fp16 bit
    patterns upcast — covers normals, subnormals, ±0, ±Inf and NaNs."""
    return np.arange(1 << 16, dtype=np.uint16).view(np.float16).astype(F32)


def _flag_oracle(xs, fmt, ref_dtype):
    """Independent flag oracle from the reference conversion: OF = finite
    input overflowed, NV = NaN input, NX = value changed (non-NaN), UF =
    tiny (below min normal, before rounding) and inexact."""
    with np.errstate(invalid="ignore"):
        ieee = xs.astype(ref_dtype).astype(F32)
    nv = np.isnan(xs)
    of = np.isinf(ieee) & np.isfinite(xs)
    nx = np.zeros(xs.shape, bool)
    m = ~nv
    nx[m] = ieee[m] != xs[m]
    uf = (xs != 0) & (np.abs(xs) < fmt.min_normal) & nx
    return ieee, of, uf, nx, nv


def _bits_equal(a, b):
    """NaN-aware bitwise comparison of f32 arrays (canonical NaN only
    needs isnan parity, finite values must match exactly incl. -0)."""
    a, b = np.asarray(a, F32), np.asarray(b, F32)
    np.testing.assert_array_equal(np.isnan(a), np.isnan(b))
    m = ~np.isnan(a)
    np.testing.assert_array_equal(a[m], b[m])
    np.testing.assert_array_equal(np.signbit(a[m]), np.signbit(b[m]))


# ---------------------------------------------------------------------------
# cast-level: flag-producing quantization vs the ml_dtypes-derived oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt_name,ref_dtype", NATIVE)
@pytest.mark.parametrize("saturate", [False, True])
def test_cast_flags_exhaustive_vs_oracle(fmt_name, ref_dtype, saturate):
    xs = _sweep16()
    fmt = get_format(fmt_name)
    ieee, of, uf, nx, nv = _flag_oracle(xs, fmt, ref_dtype)
    y, fl = softfloat.quantize_with_flags(jnp.asarray(xs), fmt,
                                          saturate=saturate)
    want = ieee
    if saturate:
        want = np.where(of, np.copysign(F32(fmt.max_normal), xs), ieee)
    _bits_equal(y, want)
    np.testing.assert_array_equal(np.asarray(fl["of"]), of)
    np.testing.assert_array_equal(np.asarray(fl["uf"]), uf)
    np.testing.assert_array_equal(np.asarray(fl["nx"]), nx)
    np.testing.assert_array_equal(np.asarray(fl["nv"]), nv)


def test_flag_invariants_fp8():
    """OF implies NX (the overflowed value is by definition inexact), UF
    implies NX, saturation changes the VALUE of overflowed elements only
    and never the telemetry."""
    xs = _sweep16()
    fmt = get_format("fp8")
    y0, f0 = softfloat.quantize_with_flags(jnp.asarray(xs), fmt)
    y1, f1 = softfloat.quantize_with_flags(jnp.asarray(xs), fmt,
                                           saturate=True)
    of = np.asarray(f0["of"])
    assert not np.any(of & ~np.asarray(f0["nx"]))
    assert not np.any(np.asarray(f0["uf"]) & ~np.asarray(f0["nx"]))
    for name in softfloat.FLAG_NAMES:
        np.testing.assert_array_equal(np.asarray(f0[name]),
                                      np.asarray(f1[name]))
    diff = np.asarray(y0) != np.asarray(y1)
    diff &= ~(np.isnan(np.asarray(y0)) & np.isnan(np.asarray(y1)))
    np.testing.assert_array_equal(diff, of)
    assert np.isfinite(np.asarray(y1)[np.isfinite(xs)]).all()


@pytest.mark.parametrize("fmt_name", ["fp8", "fp16", "fp16alt", "fp8_e4m3"])
@pytest.mark.parametrize("saturate", [False, True])
def test_quant_common_matches_ftz_oracle(fmt_name, saturate):
    """The kernels' bit-twiddling cast (quant_common, the FTZ flavor used
    by the MXU input stage) agrees bitwise with its documented oracle —
    ``softfloat.quantize`` + flush-to-zero for the value, and
    ``ref._flag_masks_ref`` for the masks — both overflow modes."""
    fmt = get_format(fmt_name)
    xs = _sweep16()
    y, of, uf, nx, nv = quantize_flag_masks(jnp.asarray(xs), fmt,
                                            saturate=saturate)
    want = np.asarray(ref._ftz(softfloat.quantize(
        jnp.asarray(xs), fmt, saturate=saturate), fmt))
    _bits_equal(y, want)
    oracle = ref._flag_masks_ref(jnp.asarray(xs), fmt)
    for got, w, name in zip((of, uf, nx, nv), oracle, softfloat.FLAG_NAMES):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w),
                                      err_msg=name)
    _bits_equal(quantize_rne_bits(jnp.asarray(xs), fmt, saturate=saturate),
                want)


# ---------------------------------------------------------------------------
# kernel-level: per-row flag accumulation vs the ref.py oracles
# ---------------------------------------------------------------------------
def _mixed(shape, seed):
    """Log-uniform magnitudes across ~13 decades: exercises OF (beyond
    fp8's 61440 rounding boundary), UF (below 2^-14) and NX everywhere."""
    rs = np.random.RandomState(seed)
    x = rs.randn(*shape).astype(F32)
    return (x * (10.0 ** rs.uniform(-7, 6, size=shape))).astype(F32)


def _scrambled_table(rows, nk, n_pages, seed=0):
    perm = np.random.RandomState(seed).permutation(n_pages)[:rows * nk]
    return perm.reshape(rows, nk).astype(np.int32)


def _scatter_pages(x, table, page):
    rows, s, d = x.shape
    nk = table.shape[1]
    pool = np.zeros((int(table.max()) + 1, page, d), F32)
    for h in range(rows):
        for j in range(nk):
            pool[table[h, j]] = x[h, j * page:(j + 1) * page]
    return jnp.asarray(pool)


def test_decode_flags_ragged_vs_ref():
    """Contiguous decode, per-row lengths incl. an EMPTY row and a
    partial block: kernel counters == oracle per row; the zero-length row
    reports zero; output is bit-identical with telemetry on."""
    lens = [0, 1, 77, 256]
    q = jnp.asarray(_mixed((4, 8, 64), seed=1))
    k = jnp.asarray(_mixed((4, 256, 64), seed=2))
    v = jnp.asarray(_mixed((4, 256, 64), seed=3))
    kvl = jnp.asarray(lens, jnp.int32)
    kw = dict(bk=128, scale=0.125, kv_fmt_name="fp8", q_fmt_name="fp8",
              src_dtype=jnp.float32, out_dtype=jnp.float32)
    o, fl = decode_attention_pallas(q, k, v, kvl, debug_flags=True, **kw)
    want = ref.decode_flag_counts_ref(q, k, v, kv_len=np.asarray(lens),
                                      kv_fmt_name="fp8", q_fmt_name="fp8")
    got = np.asarray(fl).reshape(4, -1, 4).sum(axis=1)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert got.sum() > 0 and got[2:].min(axis=0)[:3].min() > 0  # OF/UF/NX
    assert (got[0] == 0).all()                   # empty row: zero flags
    o_plain = decode_attention_pallas(q, k, v, kvl, **kw)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_plain))


def test_decode_flags_dead_slots_contribute_zero():
    """Poisoning every position >= kv_len with Inf/NaN changes neither
    the counters nor the output: dead slots are invisible."""
    lens = [40, 130]
    q = jnp.asarray(_mixed((2, 8, 64), seed=4))
    k = _mixed((2, 256, 64), seed=5)
    v = _mixed((2, 256, 64), seed=6)
    kw = dict(bk=128, scale=0.125, kv_fmt_name="fp8",
              src_dtype=jnp.float32, out_dtype=jnp.float32,
              debug_flags=True)
    kvl = jnp.asarray(lens, jnp.int32)
    o0, f0 = decode_attention_pallas(q, jnp.asarray(k), jnp.asarray(v),
                                     kvl, **kw)
    for b, L in enumerate(lens):
        k[b, L:], v[b, L:] = np.inf, np.nan
    o1, f1 = decode_attention_pallas(q, jnp.asarray(k), jnp.asarray(v),
                                     kvl, **kw)
    np.testing.assert_array_equal(np.asarray(f0), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))


def test_decode_flags_paged_scrambled_vs_ref():
    """Paged decode through a scrambled physical page layout: the flag
    walk follows the block table and still matches the gather oracle."""
    lens = [1, 77, 129, 256]
    page = 128
    q = jnp.asarray(_mixed((4, 8, 64), seed=7))
    k = _mixed((4, 256, 64), seed=8)
    v = _mixed((4, 256, 64), seed=9)
    bt = _scrambled_table(4, 256 // page, 16, seed=1)
    kp, vp = _scatter_pages(k, bt, page), _scatter_pages(v, bt, page)
    kvl = jnp.asarray(lens, jnp.int32)
    kw = dict(scale=0.125, kv_fmt_name="fp8", src_dtype=jnp.float32,
              out_dtype=jnp.float32)
    o, fl = decode_attention_pallas(q, kp, vp, kvl, jnp.asarray(bt),
                                    bk=page, debug_flags=True, **kw)
    want = ref.decode_flag_counts_paged_ref(
        q, kp, vp, jnp.asarray(bt), kv_len=np.asarray(lens),
        kv_fmt_name="fp8")
    got = np.asarray(fl).reshape(4, -1, 4).sum(axis=1)
    np.testing.assert_array_equal(got, np.asarray(want))
    o_plain = decode_attention_pallas(q, kp, vp, kvl, jnp.asarray(bt),
                                      bk=page, **kw)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_plain))


def test_flash_flags_ragged_vs_ref():
    """Flash prefill, ragged lengths, grouped heads: per-VISIT counters
    along the pruned causal schedule == the oracle's walk, per row."""
    lens = [100, 256]
    group = 2
    q = jnp.asarray(_mixed((4, 256, 64), seed=10))
    k = jnp.asarray(_mixed((2, 256, 64), seed=11))
    v = jnp.asarray(_mixed((2, 256, 64), seed=12))
    kvl = jnp.asarray(np.repeat(lens, group), jnp.int32)
    kw = dict(group=group, scale=0.125, causal=True, src_fmt_name="fp8",
              src_dtype=jnp.float32, out_dtype=jnp.float32)
    o, fl = flash_attention_pallas(q, k, v, kvl, bq=128, bk=128,
                                   debug_flags=True, **kw)
    want = ref.flash_flag_counts_ref(q, k, v, group=group,
                                     kv_len=np.repeat(lens, group),
                                     causal=True, src_fmt_name="fp8",
                                     bq=128, bk=128)
    got = np.asarray(fl).reshape(4, -1, 4).sum(axis=1)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert got.sum() > 0
    o_plain = flash_attention_pallas(q, k, v, kvl, bq=128, bk=128, **kw)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_plain))


def test_flash_flags_paged_scrambled_vs_ref():
    lens = [100, 256]
    page = 128
    q = jnp.asarray(_mixed((2, 256, 64), seed=13))
    k = _mixed((2, 256, 64), seed=14)
    v = _mixed((2, 256, 64), seed=15)
    bt = _scrambled_table(2, 256 // page, 8, seed=2)
    kp, vp = _scatter_pages(k, bt, page), _scatter_pages(v, bt, page)
    kvl = jnp.asarray(lens, jnp.int32)
    kw = dict(group=1, scale=0.125, causal=True, src_fmt_name="fp8",
              src_dtype=jnp.float32, out_dtype=jnp.float32)
    o, fl = flash_attention_pallas(q, kp, vp, kvl, jnp.asarray(bt),
                                   bq=128, bk=page, debug_flags=True, **kw)
    want = ref.flash_flag_counts_paged_ref(
        q, kp, vp, jnp.asarray(bt), bq=128, kv_len=np.asarray(lens),
        causal=True, src_fmt_name="fp8", group=1)
    got = np.asarray(fl).reshape(2, -1, 4).sum(axis=1)
    np.testing.assert_array_equal(got, np.asarray(want))


def test_ops_return_flags_reduction():
    """The ops wrapper reduces kernel cells to per-SEQUENCE [B, 4] and
    keeps the output bit-identical to the flags-off call."""
    pol = get_policy("em_fp8").replace(kv_fmt="fp8")
    lens = [33, 256]
    q = jnp.asarray(_mixed((2, 4, 1, 64), seed=16))
    k = jnp.asarray(_mixed((2, 2, 256, 64), seed=17))
    v = jnp.asarray(_mixed((2, 2, 256, 64), seed=18))
    kvl = jnp.asarray(lens, jnp.int32)
    o, fl = kops.decode_attention(q, k, v, kv_len=kvl, policy=pol,
                                  interpret=True, return_flags=True)
    o_plain = kops.decode_attention(q, k, v, kv_len=kvl, policy=pol,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_plain))
    want = ref.decode_flag_counts_ref(
        q.reshape(2 * 2, 2, 64), k.reshape(2 * 2, 256, 64),
        v.reshape(2 * 2, 256, 64), kv_len=np.repeat(lens, 2),
        kv_fmt_name="fp8", q_fmt_name="fp8")
    np.testing.assert_array_equal(
        np.asarray(fl), np.asarray(want).reshape(2, 2, 4).sum(axis=1))


# ---------------------------------------------------------------------------
# write-path ladder: per-row saturating quantization + OF/UF pressure
# ---------------------------------------------------------------------------
def test_quantize_kv_rows_ladder():
    esc = EscalationPolicy()
    fmts = esc.formats
    x = jnp.asarray(_mixed((3, 2, 4, 16), seed=19))
    levels = jnp.asarray([0, 1, 2], jnp.int32)
    y, counts = quantize_kv_rows(x, fmts, levels)
    assert np.isfinite(np.asarray(y)).all()      # saturating: never Inf
    for b, fmt in enumerate(fmts):
        want = ref._ftz(softfloat.quantize(x[b], fmt, saturate=True), fmt)
        np.testing.assert_array_equal(np.asarray(y[b]), np.asarray(want))
        of, uf, _, _ = ref._flag_masks_ref(x[b], fmt)
        assert int(counts[b, 0]) == int(jnp.sum(of))
        assert int(counts[b, 1]) == int(jnp.sum(uf))
    # narrowest rung overflows on these magnitudes, the top rung must not
    assert int(counts[0, 0]) > 0 and int(counts[2, 0]) == 0


# ---------------------------------------------------------------------------
# engine: flag-driven escalation + SDC-checked swap
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def esc_setup():
    from conftest import cached_model
    return cached_model("gemma2-9b", policy="fp32", paged_kv=True,
                        page_size=16)


def _mk_reqs(vocab, n=2, plen=12, budget=16, seed=0, **kw):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, tokens=rng.randint(0, vocab, size=plen).tolist(),
                    max_new=budget, arrival=0, **kw) for i in range(n)]


def _esc_engine(model, params, plan, policy=None, **kw):
    return ContinuousEngine(model, params, slots=2, max_len=64, chunk=16,
                            n_pages=10, burst_cap=4,
                            escalate=policy or EscalationPolicy(
                                of_threshold=4),
                            fault_plan=plan, **kw)


def test_escalation_finishes_wider_with_no_poison(esc_setup):
    """THE acceptance scenario: an overflow-injected request under the
    escalation policy drains its FULL budget, ends at a wider KV rung,
    and never trips the non-finite-logits guard (saturating writes keep
    the logits finite while pressure accumulates)."""
    model, params = esc_setup
    reqs = _mk_reqs(model.cfg.vocab)
    plan = ServeFaultPlan(overflow_at=(2,), overflow_scale=65536.0)
    fin, stats = _esc_engine(model, params, plan).run(reqs)
    assert stats["escalations"] >= 1
    assert stats["poisoned_rounds"] == 0
    assert any(f.escalated >= 1 for f in fin)
    assert len(fin) == len(reqs)
    for r, f in zip(reqs, fin):
        assert len(f.tokens) == r.max_new
    kinds = [k for k, _ in plan.events]
    assert "overflow" in kinds and "escalate" in kinds


def test_escalation_replay_deterministic(esc_setup):
    model, params = esc_setup
    reqs = _mk_reqs(model.cfg.vocab)
    plan = ServeFaultPlan(overflow_at=(2,), overflow_scale=65536.0)
    fin1, st1 = _esc_engine(model, params, plan).run(reqs)
    ev1 = list(plan.events)
    fin2, st2 = _esc_engine(model, params, plan).run(reqs)
    assert [f.tokens for f in fin1] == [f.tokens for f in fin2]
    assert st1["escalations"] == st2["escalations"]
    assert ev1 == plan.events


def test_escalation_refusable(esc_setup):
    """``no_escalate`` requests ride out the pressure at their rung: the
    refusal is counted once, the row finishes un-escalated (saturation
    still protects the logits), and its budget is honoured."""
    model, params = esc_setup
    reqs = _mk_reqs(model.cfg.vocab, no_escalate=True)
    plan = ServeFaultPlan(overflow_at=(2,), overflow_scale=65536.0)
    fin, stats = _esc_engine(model, params, plan).run(reqs)
    assert stats["escalations"] == 0 and stats["esc_refused"] >= 1
    assert all(f.escalated == 0 for f in fin)
    assert all(len(f.tokens) == r.max_new for r, f in zip(reqs, fin))


def test_escalation_deferred_under_page_pressure(esc_setup):
    """A free-list shorter than ``min_free_pages`` defers escalation (an
    escalating row re-prefills its whole history — the worst moment to
    fight admission for pages); the run still drains."""
    model, params = esc_setup
    reqs = _mk_reqs(model.cfg.vocab)
    plan = ServeFaultPlan(overflow_at=(2,), overflow_scale=65536.0)
    pol = EscalationPolicy(of_threshold=4, min_free_pages=1000)
    fin, stats = _esc_engine(model, params, plan, policy=pol).run(reqs)
    assert stats["escalations"] == 0 and stats["esc_deferred"] >= 1
    assert all(len(f.tokens) == r.max_new for r, f in zip(reqs, fin))


def test_escalation_requires_wide_pool(esc_setup):
    """A narrow-container pool policy (kv_fmt set) cannot host the
    write-time rung selection — constructing the engine must refuse."""
    from conftest import cached_model
    model8, params8 = cached_model("gemma2-9b", policy="tp_bf16_kv8",
                                   paged_kv=True, page_size=16)
    with pytest.raises(ValueError, match="escalat"):
        ContinuousEngine(model8, params8, slots=2, max_len=64, chunk=16,
                         escalate=EscalationPolicy())


@pytest.fixture(scope="module")
def swap_setup():
    from conftest import cached_model
    model, params = cached_model("gemma2-9b", paged_kv=True, page_size=16)
    rng = np.random.RandomState(0)
    mk = lambda n: rng.randint(0, model.cfg.vocab, size=n).tolist()
    reqs = [Request(rid=0, tokens=mk(20), max_new=12, arrival=0),
            Request(rid=1, tokens=mk(20), max_new=12, arrival=0),
            Request(rid=2, tokens=mk(16), max_new=8, arrival=4, priority=2)]
    return model, params, reqs


def test_sdc_detected_and_recovered_bit_exact(swap_setup):
    """Every injected swap-payload bit flip is caught by the swap-in
    checksum and recovered via free-and-reingest — tokens bit-identical
    to the same pressure scenario without corruption."""
    model, params, reqs = swap_setup
    plan = ServeFaultPlan(corrupt_swap_at=(0,))
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16,
                           n_pages=5, preempt="swap", fault_plan=plan)
    fin, stats = eng.run(reqs)
    assert stats["preempt_swap"] >= 1
    assert stats["sdc_injected"] >= 1
    assert stats["sdc_injected"] == stats["sdc_detected"]
    assert stats["sdc_detected"] == stats["sdc_reingest"]
    kinds = [k for k, _ in plan.events]
    assert kinds.count("sdc_inject") == kinds.count("sdc_detect")
    clean = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16,
                             n_pages=5, preempt="swap")
    fin_c, stats_c = clean.run(reqs)
    assert stats_c["sdc_injected"] == stats_c["sdc_detected"] == 0
    assert len(fin) == len(reqs)
    assert [f.tokens for f in fin] == [f.tokens for f in fin_c]


def test_clean_swap_checksums_verify_silently(swap_setup):
    """Without injection, checksums verify on every swap-in and the SDC
    counters stay zero (the verification itself must not misfire)."""
    model, params, reqs = swap_setup
    plan = ServeFaultPlan()          # no corruption listed
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16,
                           n_pages=5, preempt="swap", fault_plan=plan)
    fin, stats = eng.run(reqs)
    assert stats["preempt_swap"] >= 1 and stats["resumed"] >= 1
    assert stats["sdc_detected"] == 0 and stats["sdc_reingest"] == 0


# ---------------------------------------------------------------------------
# restart hygiene: fresh monitor state per attempt
# ---------------------------------------------------------------------------
def test_run_with_restarts_resets_monitors():
    """Each attempt must start with a FRESH watchdog and straggler
    monitor even when the factory reuses one runner object — a pre-crash
    EWMA would mis-flag the restart's warm-up steps."""
    class Runner:
        attempts = resets = 0

        def reset_monitors(self):
            self.watchdog = ServeWatchdog(patience=5)
            self.monitor = StragglerMonitor(warmup=0)
            self.resets += 1

        def run(self):
            self.attempts += 1
            assert self.watchdog.stalled == 0
            assert self.monitor.ewma is None and not self.monitor.flagged
            # dirty both, then crash once
            self.watchdog.stalled = 4
            self.monitor.record(0, 1.0)
            self.monitor.record(1, 99.0)
            assert self.monitor.flagged
            if self.attempts == 1:
                raise SimulatedFailure("injected")

    r = Runner()
    runner, restarts = run_with_restarts(lambda: r, max_restarts=2)
    assert runner is r and restarts == 1
    assert r.attempts == 2 and r.resets == 2


def test_engine_run_resets_its_monitors(swap_setup):
    """The engine exposes ``reset_monitors`` (the run_with_restarts
    contract) and every ``run()`` builds fresh monitor objects, so stale
    stall counts can't trip the watchdog on a healthy rerun."""
    model, params, reqs = swap_setup
    eng = ContinuousEngine(model, params, slots=2, max_len=48, chunk=16,
                           n_pages=5, preempt="swap")
    w0, m0 = eng.watchdog, eng.monitor
    w0.stalled = 10 ** 9             # poison pre-run state
    eng.run(reqs)
    assert eng.watchdog is not w0 and eng.monitor is not m0
    assert eng.watchdog.stalled < eng.watchdog.patience
