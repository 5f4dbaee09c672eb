#!/usr/bin/env bash
# Tier-1 verification gate (ROADMAP.md) — run this before every PR.
# CI and humans must invoke the same command; add flags here, not in CI.
#
#   scripts/check.sh                run the full tier-1 test suite
#   scripts/check.sh fast           the iteration tier (<1 min): the
#                                   conformance suite + core fast tests,
#                                   skipping @slow and @subprocess tests
#   scripts/check.sh bench          benchmark smoke mode: fig16 engine
#                                   throughput on a 1×CPU mesh
#                                   -> BENCH_engine.json
#   scripts/check.sh bench pipeline chunk-pipeline overlap: pipelined vs
#                                   serial wall clock, per-lane timings,
#                                   bit-identity check
#                                   -> BENCH_pipeline.json
#   scripts/check.sh bench serving  reduction-service concurrency: latency
#                                   p50/p99 + goodput at >=3 offered loads,
#                                   batch fill ratio vs batch window, PLUS
#                                   the socket-mode run: per-priority
#                                   p50/p99 over the wire protocol and the
#                                   interactive-under-bulk-saturation bound
#                                   -> BENCH_serving.json
#   scripts/check.sh bench tuner    auto-tuner validation: auto vs best/worst
#                                   fixed (chunk, window) configs per codec +
#                                   predicted-vs-measured makespan error
#                                   -> BENCH_tuner.json
#   scripts/check.sh bench io       multi-host parallel I/O: aggregated
#                                   shard writes vs file-per-rank vs single
#                                   shared file across 1/2/4 subprocess-
#                                   simulated hosts + restore pread locality
#                                   -> BENCH_io.json
#   scripts/check.sh bench progressive  progressive retrieval: bytes-fetched
#                                   vs error bound at 3+ bounds, refine-chain
#                                   prefix additivity + bit identity, prefix-
#                                   read ratio vs full container read
#                                   -> BENCH_progressive.json
#   scripts/check.sh docs           execute every fenced ```python block in
#                                   docs/*.md against the current API
set -euo pipefail
cd "$(dirname "$0")/.."
if [[ "${1:-}" == "docs" ]]; then
  shift
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/check_docs.py "$@"
  exit 0
fi
if [[ "${1:-}" == "fast" ]]; then
  shift
  # the per-iteration gate: round-trip conformance + the quick unit tiers,
  # with multi-device subprocess tests and slow model suites excluded
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q -m "not slow and not subprocess" \
      tests/test_conformance.py tests/test_pipeline.py tests/test_bitstream.py \
      tests/test_cmm.py tests/test_abstractions.py tests/test_api_portability.py \
      tests/test_tuner.py tests/test_progressive.py \
      tests/test_progressive_conformance.py \
      tests/test_wire_protocol.py tests/test_wire_fault.py \
      "$@"
  exit 0
fi
if [[ "${1:-}" == "bench" ]]; then
  shift
  if [[ "${1:-}" == "pipeline" ]]; then
    shift
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
      python -m benchmarks.fig10_13_pipeline --smoke --out BENCH_pipeline.json "$@"
    exit 0
  fi
  if [[ "${1:-}" == "serving" ]]; then
    shift
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
      python -m benchmarks.serving_load --smoke --out BENCH_serving.json "$@"
    exit 0
  fi
  if [[ "${1:-}" == "tuner" ]]; then
    shift
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
      python -m benchmarks.tuner_sweep --smoke --out BENCH_tuner.json "$@"
    exit 0
  fi
  if [[ "${1:-}" == "io" ]]; then
    shift
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
      python -m benchmarks.fig15_17_18_multinode_io --smoke --out BENCH_io.json "$@"
    exit 0
  fi
  if [[ "${1:-}" == "progressive" ]]; then
    shift
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
      python -m benchmarks.progressive_curve --smoke --out BENCH_progressive.json "$@"
    exit 0
  fi
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.fig16_scalability --smoke --out BENCH_engine.json "$@"
  exit 0
fi
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
