#!/usr/bin/env bash
# CI entry point: fast deterministic tier-1 tests (includes the SharkFrame
# API suite, the ~200-query dual-backend differential oracle, and the
# kernels_interpret-marked Pallas-route tests), a smoke run of the fluent
# SQL->ML example, the resilience leg (the seeded chaos-storm sweep — every
# fault site injected over 20 seeds against a live spill-tier server with
# byte-identical results required), and the cluster-tier leg (the
# multidevice-marked tests under
# XLA_FLAGS=--xla_force_host_platform_device_count=8).  Speed is measured
# on the chip by the benchmark in bench/, never here.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests (includes the tier1-marked frame-API suite) =="
python -m pytest -q -m tier1

echo "== frame-API smoke (fluent SQL->ML pipeline end to end) =="
python examples/sql_ml_pipeline.py

echo "== resilience: seeded chaos-storm sweep (every fault site, 20 seeds) =="
python -m pytest -q tests/test_chaos_storm.py tests/test_resilience.py

echo "== cluster tier: 8-device mesh tests =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q -m multidevice
