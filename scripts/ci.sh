#!/usr/bin/env bash
# CI entrypoint: the tier-1 tests, in named gates, and the docs check.
#
# Mirrors the tier-1 verify line in ROADMAP.md.  Serving performance is
# measured on the chip by the benchmark in bench/ (BENCHMARK.json), not
# here.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests (engine + fault modules gated separately below) =="
# includes tests/test_ragged_attention.py (per-row length plumbing) and
# tests/test_paged_attention.py (block-table indirection: paged kernels
# vs the paged oracles, allocator misuse errors, preemption-batch frees,
# prefix sharing) — all kernel tests run in Pallas interpret mode on CPU
python -m pytest -x -q --ignore=tests/test_engine.py \
    --ignore=tests/test_engine_faults.py \
    --ignore=tests/test_speculative.py \
    --ignore=tests/test_replica_ha.py

echo "== continuous-batching engine tests =="
# the PR-5 serving engine gate, run once as its own named step so a
# failure is unmissable: decode_burst==scan bit-parity, early exit,
# admission determinism, page accounting, penalties parity, no-retrace
python -m pytest -q tests/test_engine.py

echo "== serving fault / robustness tests =="
# the PR-6 overload gate: preempt-resume bit-parity (free-and-reingest
# AND swap-to-host), fp8-exact degraded swap, fault-plan replay
# determinism, deadline accounting, poisoned-logits fail-fast, watchdog
# abort, and the overload soak draining under injected faults
python -m pytest -q tests/test_engine_faults.py

echo "== speculative decoding tests =="
# the PR-9 gate: draft/verify parity — chunk-form verify bitwise equals
# sequential decode (logits AND cache bytes, contiguous + paged, across
# KV formats), the accepted stream equals plain greedy decode under
# layer-skip and narrow-format drafts, rollback leaves the live cache
# bit-identical, EOS-mid-chunk / forced-0%-accept accounting, and engine
# composition with per-request caps, preemption and escalation.
# -p no:randomly pins declaration order if pytest-randomly is ever
# installed: the module-scoped engine fixture and probe-derived stop
# tokens assume a stable order within this file.
python -m pytest -q -p no:randomly tests/test_speculative.py

echo "== replica fault-tolerance / HA tests =="
# the PR-10 gate: replica loss with token-bit-identical results — kill
# (reingest migration) and hang (CRC-tagged swap-blob migration) parity,
# no_degrade and mid-escalation victims surviving migration intact,
# foreign-blob refusal, journal replay through run_with_restarts with
# two-recovery determinism, torn-tail recovery + truncation, and the
# session-flavor HA soak draining through a kill
python -m pytest -q tests/test_replica_ha.py

echo "== numerical-health tests =="
# the PR-7 gate: IEEE flag casts vs an ml_dtypes oracle (exhaustive
# 16-bit sweep, both overflow modes), kernel flag counters under ragged
# lengths + scrambled page tables, the saturating KV write ladder,
# flag-driven escalation finishing wider with zero poison, CRC-checked
# swap detecting injected bit flips with bit-identical recovery, and
# fresh-monitor state across restarts
python -m pytest -q tests/test_numerical_health.py

echo "== mesh-sharded serving tests (8 simulated devices) =="
# the PR-8 gate, run on an 8-way forced-host-platform mesh: head-sharded
# attention bit-identical per head to single-device on EVERY route
# (dense/pallas x prefill/decode x contiguous/paged), fp32-psum row-
# parallel projections allclose (bitwise under the tp_bf16 output snap),
# full-model logits parity, engine + data-parallel replica token parity,
# version-gate shims (both branches, monkeypatched), divisibility
# fallback warnings, per-replica allocator isolation, and the shipped
# pre-warmed autotuner cache loader
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest -q tests/test_sharded_serving.py tests/test_autotune.py

echo "== docs: link + module-coverage check =="
# every public kernels/ and models/ module must be mentioned in the docs
# surface (README.md + docs/), and every relative markdown link must
# resolve — documentation that names dead files or skips live ones rots.
python - <<'EOF'
import os, re, sys

DOCS = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir("docs") if f.endswith(".md"))
text = {p: open(p).read() for p in DOCS}
errs = []

# relative links resolve (skip URLs and intra-page anchors)
for p, t in text.items():
    for m in re.finditer(r"\[[^\]]*\]\(([^)#\s]+)[^)]*\)", t):
        target = m.group(1)
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        resolved = os.path.normpath(
            os.path.join(os.path.dirname(p), target))
        if not os.path.exists(resolved):
            errs.append(f"{p}: dead link -> {target}")

# module coverage: public modules under kernels/, models/ and launch/
# (launch/engine.py — the continuous-batching scheduler — must stay on
# the documented surface) are named somewhere in the docs
blob = "\n".join(text.values())
for pkg in ("src/repro/kernels", "src/repro/models", "src/repro/launch"):
    for f in sorted(os.listdir(pkg)):
        if not f.endswith(".py") or f.startswith("_"):
            continue
        mod = f"{os.path.basename(pkg)}/{f}"
        if mod not in blob:
            errs.append(f"docs never mention {mod}")

if errs:
    sys.exit("docs check FAILED:\n  " + "\n  ".join(errs))
print(f"docs OK ({len(DOCS)} files, links + kernels/ + models/ + launch/ "
      f"coverage)")
EOF

echo "CI OK"
