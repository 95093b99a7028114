"""``chip_smoke.py`` on the CPU: its flow at reduced size, its refusal to
run anywhere but a TPU, and the compile-cache helper every entry point
calls first."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_flow_reduced():
    # the default phase at reduced size: engine vs Model.generate, with
    # every check of the chip run except kernel presence (dense on CPU)
    smoke = _load_smoke()
    model = smoke.build_model(depth=2, reduced=True)
    params = model.init(jax.random.key(0))
    reqs = smoke.make_requests(model.cfg.vocab, seed=0, n=3,
                               prompt_lens=(8, 40), new_tokens=(3, 6))
    out = smoke.run_one_chip(model, params, reqs, slots=2, chunk=16,
                             check_kernels=False)
    assert sorted(out["served"]["tokens"]) == [r.rid for r in reqs]
    # CPU engine and reference both run dense attention: greedy streams
    # agree in full, and every emitted token is the reference's argmax
    assert out["agree"] == [r.max_new for r in reqs]
    assert out["margin"] == 0.0
    assert out["compile_s"] > 0


def test_smoke_decode_check_rejects_wrong_tokens():
    # the teacher-forced decode check passes the reference's own greedy
    # tokens and fails a stream whose tokens are each off by one id
    smoke = _load_smoke()
    model = smoke.build_model(depth=2, reduced=True)
    params = model.init(jax.random.key(0))
    reqs = smoke.make_requests(model.cfg.vocab, seed=1, n=2,
                               prompt_lens=(8, 40), new_tokens=(3, 6))
    pad_to = smoke.pad_width(reqs, 16)
    ref = smoke.reference(model, params, reqs, pad_to=pad_to)
    good = smoke.forced_margins(model, params, reqs, ref["tokens"],
                                pad_to=pad_to)
    assert smoke.check_decode(reqs, good, 0.1, "greedy") == 0.0
    wrong = {rid: [(t + 1) % model.cfg.vocab for t in toks]
             for rid, toks in ref["tokens"].items()}
    bad = smoke.forced_margins(model, params, reqs, wrong, pad_to=pad_to)
    with pytest.raises(SystemExit, match="below the reference's best"):
        smoke.check_decode(reqs, bad, 0.1, "off by one")


@pytest.mark.parametrize("where", ["cpu-platform", "standalone-copy"])
def test_smoke_refuses_without_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "standalone-copy":   # no repo beside it to import
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_into_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
