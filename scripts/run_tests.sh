#!/usr/bin/env bash
# Tier-1 test suite, one command locally and in CI:
#   scripts/run_tests.sh            # whole suite
#   scripts/run_tests.sh tests/test_scheduler.py -k budget
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# the suite runs on the CPU (Pallas kernels in interpret mode) and must never
# take an attached TPU; chip runs go through chip_smoke.py
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
exec python -m pytest -q "$@"
