#!/usr/bin/env bash
# One-command static gate (r17): the contract linter over onix/,
# chip_smoke.py, and scripts/ (onix/analysis/ — exception discipline, env
# registry, counter namespaces, gate discipline, fingerprint coverage,
# jit/trace hazards, lock discipline, fault-site/doc drift; see
# docs/ROBUSTNESS.md "The contract linter"), then the native build's
# existing sanitizer test (ASan/UBSan over the C decoders via
# tests/test_native_asan.py). Extra args pass through to the analyzer:
#
#     scripts/lint.sh                       # the enforcement run
#     scripts/lint.sh --passes locks,gates  # a focused slice
#     scripts/lint.sh --write-docs          # refresh generated tables
#
# Exit is non-zero on any lint finding or sanitizer failure.
set -euo pipefail
cd "$(dirname "$0")/.."

python -m onix.analysis "$@"

# The sanitizer test builds the instrumented decoder itself and skips
# with a visible message when no compiler toolchain is available.
JAX_PLATFORMS=cpu python -m pytest tests/test_native_asan.py -q \
    -p no:cacheprovider

# Telemetry invariants (r18, docs/OBSERVABILITY.md): the
# telemetry-disabled bit-identity smoke (winners + dispatch counts
# unchanged with the layer off — the hard constraint it ships under)
# and the /metrics exposition checks against the strict in-tree
# Prometheus parser.
JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py -q \
    -k "disabled_bit_identity or metrics or render_parse or rejects" \
    -p no:cacheprovider
