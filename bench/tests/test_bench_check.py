"""The comparison that decides ``correct``, at a size the CPU holds: the
reference is the program's equations, sound runs pass, and the control and
faults planted in the timed path fail."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, weights
from bench.check import calibrate, compare, reference

FIX = Path(__file__).resolve().parent / "fixtures"
load = harness.load_json


def _cell(kind="dense"):
    spec = load(FIX / "tiny-cell.json")
    spec.update(config="tiny", traffic="tiny")
    return harness.Cell("tiny", spec, load(FIX / f"tiny-{kind}.json"),
                        load(FIX / "tiny-mix.json"))


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_reference_is_the_program_in_float32(kind):
    import jax
    import jax.numpy as jnp
    cfg = load(FIX / f"tiny-{kind}.json")
    cfg["program"]["policy"] = "fp32"
    model = harness.build_model(cfg).with_cfg(
        paged_kv=False, decode_backend="dense", prefill_backend="dense")
    params = weights.make(model, 7)
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        lg, _ = model.prefill(params, jnp.asarray(toks)[None], max_len=64)
    spec = reference.Spec.from_config(cfg)
    x = reference.hidden(params, spec, toks.tolist())
    h = reference._rms(x[39:40], params["norm_f"]["g"], spec.eps)
    ref = np.asarray(jnp.einsum("nd,dv->nv", h,
                                params["lm_head"].astype(jnp.float32),
                                precision="highest"))[0, :256]
    np.testing.assert_allclose(np.asarray(lg)[0, -1, :256], ref, atol=1e-4)


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    served = {i: ([1] * (10 + i), [2] * (5 + (i % 3))) for i in range(20)}
    a = compare.sample(served, 5, 30)
    assert a[0] == 19 and a == compare.sample(served, 5, 30)
    assert a != compare.sample(served, 6, 30)
    assert sum(len(served[r][1]) for r in a) >= 30


@pytest.fixture(scope="module")
def readings():
    return calibrate.readings(_cell(), [201, 202, 203], {201, 202, 203},
                              3.0, require_tpu=False)


def test_sound_runs_pass_and_the_control_fails(readings):
    limit = load(FIX / "tiny-cell.json")["check"]["max_gap"]
    for r in readings:
        assert r["requests"] > 0 and r["tokens"] >= 40
        assert r["correct"] and r["check"]["max_gap"] <= limit, r
        assert r["control_correct"] is False, r
        assert r["control_check"]["max_gap"] > limit, r


def test_limit_readings_summarize_each_compared_number():
    cell = _cell()
    cell.spec["check"] = {"mean_gap": 0.1, "p99_gap": 0.5}
    rows = [{"mean_gap": 0.01, "p99_gap": 0.2, "max_gap": 9.0,
             "control_mean_gap": 0.3, "control_p99_gap": 0.9},
            {"mean_gap": 0.03, "p99_gap": 0.1, "max_gap": 9.0},
            {"mean_gap": 0.02, "p99_gap": 0.3, "max_gap": 9.0,
             "control_mean_gap": 0.2, "control_p99_gap": 1.2}]
    got = calibrate.limits_readings(cell, rows)
    assert set(got) == {"mean_gap", "p99_gap"}
    assert got["mean_gap"] == {"lower": 0.03, "upper": 0.2, "seeds": 3,
                               "control_seeds": 2}
    assert got["p99_gap"]["lower"] == 0.3 and got["p99_gap"]["upper"] == 0.9
    rows = [{k: v for k, v in r.items() if not k.startswith("control_")}
            for r in rows]
    assert calibrate.limits_readings(cell, rows)["mean_gap"]["upper"] is None


def test_the_control_in_the_programs_place_is_not_correct():
    out = harness.run(_cell(), 212, 3.0, False, t_process=0.0, control=True)
    assert out["correct"] is False
    assert out["check"]["max_gap"]["value"] > out["check"]["max_gap"]["limit"]
    for k in ("unfinished", "short_answers", "window_compiles"):
        assert out["check"][k]["value"] == 0


def _broken_run(monkeypatch, fault):
    from repro.models.transformer import Model
    orig = Model.decode_burst

    def burst(self, params, tok, caches, *a, **kw):
        r = orig(self, params, tok, caches, *a, **kw)
        if fault == "token":        # a token altered where it is produced
            out = r[0]
            return ((out >= 0) * ((out + 1) % self.cfg.vocab)
                    + (out < 0) * out,) + tuple(r[1:])
        return r[:3] + (caches,) + r[4:]    # the step's state unchanged

    monkeypatch.setattr(Model, "decode_burst", burst)
    return harness.run(_cell(), 211, 3.0, False, t_process=0.0)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    out = _broken_run(monkeypatch, fault)
    assert out["correct"] is False
    assert out["check"]["max_gap"]["value"] > out["check"]["max_gap"]["limit"]
    assert list(out)[-1] == "check"
