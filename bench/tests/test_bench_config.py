"""BENCHMARK.json against the files the harness finds by name, and the
command's refusal to run without a TPU."""
import importlib.util
import json
import os
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _cells(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_name_existing_cells_and_have_readers(kind):
    for m in BENCH[kind]:
        assert NAME.match(m["name"]), m["name"]
        assert set(_cells(m)) <= set(CELLS), m["name"]
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_layer_metrics_move_a_metric_their_cells_report():
    for m in BENCH["per_layer"]:
        moved = E2E[m["moves"]]
        assert set(_cells(m)) <= set(_cells(moved)), m["name"]


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for cell in CELLS:
        e2e = [n for n, m in E2E.items() if cell in _cells(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in _cells(m) for m in BENCH["per_layer"]), cell


def test_bounds_within_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")


def test_cells_configs_and_mixes_resolve():
    for name, w in CELLS.items():
        cell = harness.load_cell(name, BENCH)
        assert cell.config["num_hidden_layers"] > 0
        assert cell.mix["arrivals"]["loop"] == "open"
        assert w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])


@pytest.mark.parametrize("config", [c["file"] for c in BENCH["configs"]])
def test_program_matches_the_published_widths(config):
    cfg = json.loads((ROOT / config).read_text())
    model = harness.build_model(cfg)     # raises on any departure
    assert model.cfg.paged_kv


def test_run_refuses_without_a_tpu(capsys, monkeypatch):
    # run.py names the compile cache in the environment; setting each
    # variable through monkeypatch records it, so it is put back afterwards
    for var in ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.setenv(var, os.environ.get(var, ""))
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  ROOT / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen3moe-chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""
