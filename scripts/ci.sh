#!/usr/bin/env bash
# One-shot merge gate (docs/STATUS.md "round 19"): everything a PR
# must hold, in the order a failure is cheapest to see.
#
#   1. tier-1 — the fast test suite on the forced-CPU jax platform
#      (the same invocation the driver scores; `-m 'not slow'` keeps
#      the chaos soaks and bench legs out of the gate);
#   2. nebulint — the nineteen-check static/semantic/flow suite, run
#      ONCE in SARIF mode with the baseline applied; the JSON lands in
#      $CI_ARTIFACT_DIR (default build/) so CI uploads it as an
#      annotation artifact, and a non-empty `results` array fails the
#      gate exactly like the plain CLI would;
#   3. nebulamc — the deterministic interleaving model checker at
#      smoke budgets, also in SARIF mode; a found violation ships its
#      replayable schedule id inside the SARIF message text and fails
#      the gate (the exhaustive sweep lives in chaos.sh);
#   4. micro_bench — the performance-budget components (`--quick`
#      statistics are noisier but the budgets are sized for it); the
#      lint cold-wall budget (40 s), the mc smoke-sweep budget, the
#      admission/recovery/absorb/continuous/timeline path budgets
#      all gate here via micro_bench's own exit status.
#
# The Perfetto golden (tests/golden_timeline.json, the byte-stable
# chrome_trace pin) rides along to $CI_ARTIFACT_DIR beside the SARIF
# artifacts so a reviewer can open the reference timeline in
# chrome://tracing without checking the branch out.
#
# scripts/lint.sh remains the interactive lint + sanitizer entry
# point; this script is the merge gate CI calls.
set -euo pipefail
cd "$(dirname "$0")/.."

ARTIFACT_DIR="${CI_ARTIFACT_DIR:-build}"
mkdir -p "${ARTIFACT_DIR}"

echo "== tier-1 (pytest, JAX_PLATFORMS=cpu) =="
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly

echo "== nebulint (SARIF artifact -> ${ARTIFACT_DIR}/nebulint.sarif) =="
JAX_PLATFORMS=cpu python -m nebula_tpu.tools.lint --format=sarif \
  > "${ARTIFACT_DIR}/nebulint.sarif"

echo "== nebulamc (SARIF artifact -> ${ARTIFACT_DIR}/nebulamc.sarif) =="
JAX_PLATFORMS=cpu python -m nebula_tpu.tools.mc run --smoke --format=sarif \
  > "${ARTIFACT_DIR}/nebulamc.sarif"

echo "== micro_bench (budget components, --quick) =="
JAX_PLATFORMS=cpu python -m nebula_tpu.tools.micro_bench --quick \
  > "${ARTIFACT_DIR}/micro_bench.json"

echo "== perfetto golden -> ${ARTIFACT_DIR}/golden_timeline.json =="
cp tests/golden_timeline.json "${ARTIFACT_DIR}/golden_timeline.json"

echo "ci.sh: merge gate green (artifacts in ${ARTIFACT_DIR}/)"
