#!/usr/bin/env bash
# Minimal CI: the static gate, tier-1 tests (the paper's claims among
# them, tests/test_paper_claims.py) and the fault-tolerance leg. Speed is
# measured on the chip by bench/, not here.
#
# CI_FORCE_DEVICES=N forces N XLA host devices BEFORE jax initializes so
# the app-sharded engine paths (shard_map over the ("app",) mesh, memo
# merges, sharded-vs-single equivalence tests) are exercised on every push.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ -n "${CI_FORCE_DEVICES:-}" ]]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=${CI_FORCE_DEVICES} ${XLA_FLAGS:-}"
fi

# dev extras (hypothesis property tests) are best-effort: the suite
# degrades gracefully without them
pip install -q -r requirements-dev.txt 2>/dev/null || true

# static-analysis gate: jaxlint (repro.analysis) — trace hygiene,
# PRNG discipline, donation safety, precision-policy conformance
# (JL001-JL006) plus the folded-in api/docstring/doc-link gates
# (JL100-JL102). Dependency-free, offline, seconds; baseline policy in
# docs/contributing.md#static-analysis
python scripts/lint.py

# estimator parity suite first (fast, no engine builds): batched
# StratumTables estimators must match the scalar reference before the
# full tier-1 run exercises everything built on them
python -m pytest -x -q tests/test_estimator_tables.py

python -m pytest -x -q

# fault-tolerance leg: the full resume-equivalence matrix (slow-marked
# scheme sweeps; the pytest.ini addopts excludes them from the tier-1
# run above, the explicit -m here overrides it). Under CI_FORCE_DEVICES=8
# this includes the sharded + elastic device-drop scenarios (multidevice
# marker); tight deadline — the whole leg is minutes, not hours
timeout 1200 python -m pytest -q -m "slow or multidevice" \
  tests/test_fault_tolerance.py
