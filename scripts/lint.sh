#!/usr/bin/env bash
# Static-analysis gates + analyzer self-tests (docs/ANALYSIS.md), a
# convenience over gates that are tier-1 tests already (tests/
# test_analysis.py, test_jaxcheck.py): raftlint (AST rules, <60s),
# jaxcheck (the device-plane program auditor: dtype/transfer/donation/
# G-last over every ops/ jit entry point, <60s on CPU) and wirecheck
# (the wire-compat auditor: golden corpus, skew matrix, 500-mutation
# decoder fuzz, registry rot guards, <30s) each fail on any finding
# not covered by their checked-in baselines, then the analyzer
# self-tests prove all three still catch seeded violations
# (true-positive fixtures) and that the lock-order witness detects an
# inverted acquisition.
cd "$(dirname "$0")/.." || exit 1
set -o pipefail
rc=0
timeout -k 5 60 env JAX_PLATFORMS=cpu python -m dragonboat_tpu.analysis \
    --baseline dragonboat_tpu/analysis/baseline.txt dragonboat_tpu \
    || rc=1
timeout -k 5 60 env JAX_PLATFORMS=cpu python -m dragonboat_tpu.analysis \
    --jax --baseline dragonboat_tpu/analysis/jax_baseline.txt \
    || rc=1
timeout -k 5 60 env JAX_PLATFORMS=cpu python -m dragonboat_tpu.analysis \
    --wire --baseline dragonboat_tpu/analysis/wire_baseline.txt \
    || rc=1
timeout -k 5 150 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_analysis.py tests/test_invariants.py tests/test_jaxcheck.py \
    -q -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
exit $rc
