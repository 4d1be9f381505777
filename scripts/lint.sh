#!/usr/bin/env bash
# graftlint wrapper: run the project-invariant static analysis over the
# repo surface (or the given paths). Exit 0 clean, 1 findings.
#
#   scripts/lint.sh            # full sweep (DEFAULT_TARGETS)
#   scripts/lint.sh --json     # machine-readable report
#   scripts/lint.sh deeplearning4j_tpu/serving
#
# jax-free and fast (~2s): safe to run any time.
set -euo pipefail
cd "$(dirname "$0")/.."
exec python -m deeplearning4j_tpu.analysis "$@"
