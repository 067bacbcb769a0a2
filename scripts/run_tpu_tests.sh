#!/bin/sh
# Real-TPU correctness pass: the golden relational ops with COMPILED
# (non-interpreted) Pallas kernels on the attached chip. One process;
# the exit code is pytest's.
cd "$(dirname "$0")/.." || exit 1
CYLON_TPU_TESTS=1 exec python -m pytest tests/test_tpu_golden.py -m tpu \
    -q --tb=short "$@"
