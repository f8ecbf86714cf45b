#!/usr/bin/env bash
# Tiered CI entry point (run by .github/workflows/ci.yml, and locally):
#
#   scripts/ci.sh --fast   fast gate: repro-lint + pytest -m "not slow" +
#                          interpret-mode kernel smoke (decode/context/
#                          verify) + the spec==greedy smoke + the
#                          quantized-KV smoke (fused-dequant kernels +
#                          int8-pool serving) + the tiered cluster-prefix
#                          smoke + the observability (HexTrace) smoke +
#                          the KVSAN serving smoke
#                          (~5 min on a laptop CPU)
#   scripts/ci.sh --full   everything: full pytest (incl. @slow multi-device
#                          subprocess sweeps), every serving smoke on 4
#                          virtual devices (continuous/paged/prefix/disagg/
#                          spec) plus the whole set again under the KVSAN
#                          lifecycle sanitizer, the launch.serve --trace-out
#                          smoke gated by the repro.obs.report CLI, and the
#                          benchmark-results + oracle-registry schema guard
#
# No flag defaults to --full (the historical behavior). The smokes
# themselves live in scripts/smoke_serving.py so humans can run or debug
# one suite directly without replaying the whole gate.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

TIER="${1:---full}"
case "$TIER" in
  --fast|--full) ;;
  *) echo "usage: $0 [--fast|--full]" >&2; exit 2 ;;
esac

echo "=== repro-lint (repo-specific static analysis) ==="
# pure-AST pass: clock discipline, jit-retrace hazards, kernel/oracle
# registry coverage, refcount pairing, hygiene — seconds, so every tier
python -m repro.analysis.lint src

if [[ "$TIER" == "--fast" ]]; then
  echo "=== tier-1 pytest (fast: -m 'not slow') ==="
  python -m pytest -x -q -m "not slow"
else
  echo "=== tier-1 pytest (full) ==="
  # deliberately the exact command ROADMAP.md names as the tier-1 gate
  python -m pytest -x -q
fi

echo "=== paged-attention kernels (Pallas interpret mode) ==="
# the paged decode + context-prefill + multi-token verification kernels
# with the Pallas backend engaged in interpret mode (GPU-less CI's only
# route through the block-table index maps); ops.backend() restores the
# global on error
python scripts/smoke_serving.py kernels

echo "=== speculative-decoding smoke (4 virtual devices) ==="
# spec == greedy token identity on the multi-device pipeline gates every
# tier: speculation must never change WHICH tokens serving produces
python scripts/smoke_serving.py spec

echo "=== quantized-KV smoke (interpret kernels + int8-pool serving) ==="
# the exactness gate for fused dequant (vs the unquantized kernels on
# materialized-dequant pages: int8 bitwise, fp8 within 4 f32 ulps) plus
# int8 page pools end to end
python scripts/smoke_serving.py quant

echo "=== tiered cluster-prefix smoke (2 replicas, 4 virtual devices) ==="
# host-tier spill + shared-directory fetch + prefix-aware routing must
# stay token-identical to cold paged serving in every tier
python scripts/smoke_serving.py cluster

echo "=== observability smoke (HexTrace spans + metrics + report CLI) ==="
# a traced + metered serve must reproduce the untraced run token for
# token, and its Chrome-trace/metrics exports must pass the report CLI's
# schema gate — tracing is pure observation in every tier
python scripts/smoke_serving.py obs

if [[ "$TIER" == "--fast" ]]; then
  echo "=== KVSAN serving + chaos smoke (page-lifecycle sanitizer) ==="
  # the paged + prefix suites again under KVSAN, plus the online-
  # rescheduling chaos suite: a replica kill mid-request and a live role
  # migration mid-decode must stay token-identical to the cold runs with
  # zero page leaks through evacuation and migration
  python scripts/smoke_serving.py serving prefix chaos --kvsan
fi

if [[ "$TIER" == "--full" ]]; then
  echo "=== serving smokes (4 virtual devices) ==="
  python scripts/smoke_serving.py serving prefix disagg chaos

  echo "=== KVSAN serving smokes (page-lifecycle sanitizer) ==="
  # every serving suite again with the sanitizer shadowing the pools
  python scripts/smoke_serving.py serving prefix disagg cluster spec quant \
    obs chaos --kvsan

  echo "=== trace smoke (launch.serve --trace-out -> report CLI gate) ==="
  # the full CLI spine with tracing on: serve, export a Chrome trace +
  # metrics JSONL + the predicted-vs-observed calibration table, then
  # gate the artifacts on the report CLI's schema validation
  TRACE_TMP="$(mktemp -d)"
  trap 'rm -rf "$TRACE_TMP"' EXIT
  python -m repro.launch.serve --arch granite-8b --reduced \
    --cluster case_study --rate 4 --duration 1 --deadline 60 \
    --out-len 4 --search-iters 2 --policy continuous \
    --block-size 8 \
    --trace-out "$TRACE_TMP/trace.json" \
    --metrics-out "$TRACE_TMP/metrics.jsonl" --calibrate
  python -m repro.obs.report "$TRACE_TMP/metrics.jsonl" \
    --trace "$TRACE_TMP/trace.json" \
    --require-spans serve,queue_wait,iteration,prefill,decode

  echo "=== benchmark results + oracle registry schema guard ==="
  python -m benchmarks.run --check
fi

echo "=== ci.sh $TIER OK ==="
