#!/usr/bin/env bash
# CI entry point: install dependencies and run the tier-1 verification.
#
#   ./scripts/ci.sh          install deps (unless SKIP_INSTALL=1), run tests
#
# Mirrors ROADMAP.md's tier-1 command exactly; keep the two in sync.
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${SKIP_INSTALL:-0}" != "1" ]]; then
    python -m pip install --upgrade pip
    python -m pip install -r requirements.txt
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Static-analysis leg (fedlint): enforce the ledger/PRNG/carry/kernel
# contracts at the AST level over everything CI ships. Exits nonzero on any
# finding; the JSON report is uploaded as a CI artifact by the workflow.
# --check-docs also fails the leg if docs/analysis.md and the registered
# rule set drift apart.
mkdir -p benchmarks/out
ANALYSIS=1 python -m repro.analysis src benchmarks examples \
    --check-docs docs/analysis.md \
    --format json --out benchmarks/out/fedlint.json

python -m pytest -x -q

# Interpret-mode kernel leg: force the dispatch layer's "auto" onto the
# Pallas (interpreter) path so the kernel hot loops — not the jnp
# reference — back the engine while the federated-core suites run.
# Catches kernel regressions the reference-backed tier-1 run can't see.
REPRO_KERNEL_BACKEND=pallas python -m pytest -x -q \
    tests/test_kernels.py tests/test_dispatch.py tests/test_core_fednew.py

# Declarative-API leg: run a tiny spec end to end through the CLI so the
# JSON schema and `python -m repro.api` cannot silently rot. The RunResult
# JSON is uploaded as a CI artifact by the workflow.
mkdir -p benchmarks/out
python -m repro.api examples/specs/quickstart.json \
    --out benchmarks/out/quickstart_runresult.json

# LM-workload smoke leg: a tiny-transformer kind='model' spec (registry
# arch at reduced size) through the CLI — matrix-free FedNew over a param
# pytree with per-leaf exact ledgers. The artifact checker asserts the
# RunResult schema: int ledgers, ledger/metric agreement, decreasing loss.
python -m repro.api examples/specs/lm_tiny.json \
    --out benchmarks/out/lm_tiny_runresult.json
python scripts/check_lm_artifact.py benchmarks/out/lm_tiny_runresult.json

# x64 leg: the int64 bits_metric_dtype branch of the exact uplink ledger is
# dead code under default-f32 CI. Re-run the quantization/ledger suites with
# x64 enabled, then push one float64 spec through the CLI (which flips x64
# itself) so 64-bit word accounting and the JSON int ledger are exercised
# end to end.
JAX_ENABLE_X64=1 python -m pytest -x -q \
    tests/test_quantization.py tests/test_api.py tests/test_comm.py
python -m repro.api examples/specs/float64_smoke.json \
    --out benchmarks/out/float64_runresult.json

# Benchmarks smoke leg: run the comm-tradeoff suite at tiny dims (3 codecs,
# a few rounds) through the real benchmark harness, then assert the
# artifact's JSON schema — the frontier emitter and the exact downlink /
# simulated-time plumbing cannot silently rot.
COMM_SMOKE=1 BENCH_ROUNDS=4 python -m benchmarks.run --only comm_tradeoff
python scripts/check_comm_artifact.py benchmarks/out/comm_tradeoff.json

# Solver-conformance leg: the registry-wide battery (scan-vs-host,
# shard_map-vs-scan, empty-round freeze, fraction=1.0 short-circuit, exact
# ledger/metric agreement) on a forced 8-device host mesh, so the sharded
# schedule runs with a real 8-way client axis instead of the size-1 axis a
# 1-CPU runner would give it.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q tests/test_solver_conformance.py

# Cross-solver frontier smoke leg: solver x codec x participation sweep at
# tiny dims through the real harness, schema-checked — the zoo's exact
# ledgers, netsim pricing, and the frontier artifact cannot silently rot.
SOLVER_SMOKE=1 BENCH_ROUNDS=4 python -m benchmarks.run --only solver_frontier
python scripts/check_frontier_artifact.py benchmarks/out/solver_frontier.json

# Event-runtime smoke leg: the sync-vs-async frontier at tiny dims through
# the real harness (streamed cohorts, the event heap, buffered-async
# FedNew), schema-checked — the event clock, staleness weighting, and the
# O(sampled) state accounting cannot silently rot. The tracked repo-root
# headline point (BENCH_async_frontier.json) is validated against the same
# schema so a stale refresh fails here too.
EVENTS_SMOKE=1 BENCH_ROUNDS=4 python -m benchmarks.run --only async_frontier
python scripts/check_async_artifact.py benchmarks/out/async_frontier.json
python scripts/check_async_artifact.py BENCH_async_frontier.json

# Telemetry smoke leg: a traced+profiled scan run and a traced events run
# through the CLI, their trace files structurally validated (both clock
# domains present) by the telemetry CLI, plus the roofline suite at tiny
# rounds with its artifact schema-checked — the trace format, the
# diagnostics stream, and the HLO-cost roofline plumbing cannot silently
# rot.
python -m repro.api examples/specs/traced_quickstart.json \
    --out benchmarks/out/traced_quickstart_runresult.json
python -m repro.telemetry validate benchmarks/out/traced_quickstart_trace.json \
    --expect-domain host --expect-domain sim \
    --stream benchmarks/out/traced_quickstart_stream.jsonl
python -m repro.api examples/specs/traced_events.json \
    --out benchmarks/out/traced_events_runresult.json
python -m repro.telemetry validate benchmarks/out/traced_events_trace.json \
    --expect-domain host --expect-domain sim
python -m repro.telemetry summarize benchmarks/out/traced_quickstart_trace.json
TELEMETRY_SMOKE=1 BENCH_ROUNDS=4 python -m benchmarks.run --only roofline_bench
python scripts/check_roofline_artifact.py benchmarks/out/roofline_bench.json
