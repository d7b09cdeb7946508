#!/usr/bin/env bash
# Build-time gate: meshlint (wire-protocol / async-safety / JAX-hygiene
# static analysis, docs/ANALYSIS.md) + a bytecode compile sweep. Run from
# anywhere; CI and run.sh call this. Exit nonzero on any new finding.
set -euo pipefail
cd "$(dirname "$0")/.."
PY="${PYTHON:-python}"

echo "[lint] meshlint (python -m bee2bee_tpu.analysis)"
"$PY" -m bee2bee_tpu.analysis "$@"

echo "[lint] compileall"
"$PY" -m compileall -q bee2bee_tpu

# benchdiff self-check (docs/PERF.md): the perf-regression CI gate's own
# contract suite — regression trips, cross-platform comparison refuses.
# SKIP_BENCHDIFF=1 skips it.
if [ "${SKIP_BENCHDIFF:-0}" != "1" ]; then
  echo "[lint] benchdiff self-check"
  "$PY" scripts/benchdiff.py --self-check

  # model-tier speculative-decoding gate (docs/PERF.md "Model-tier
  # speculative decoding"): re-run the spec_model rung and diff against
  # the recorded round-19 baseline. The headline is acceptance-weighted
  # tok/s for the resident model drafter; the rung also re-certifies the
  # mesh cell's typed degradation (kill mid-generation, zero drops).
  # Threshold 0.5: the metric multiplies tok/s by acceptance, so shared-
  # CPU noise compounds; the off/ngram cells this must beat sit at ~0.
  echo "[lint] spec_model rung vs BENCH_spec_model_r01.json"
  FRESH="$(mktemp "${TMPDIR:-/tmp}/spec_model.XXXXXX.json")"
  JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    "$PY" bench.py spec_model | tail -1 > "$FRESH"
  rc=0
  "$PY" scripts/benchdiff.py BENCH_spec_model_r01.json "$FRESH" \
    --threshold 0.5 || rc=$?
  rm -f "$FRESH"
  if [ "$rc" -ne 0 ] && [ "$rc" -ne 2 ]; then
    echo "[lint] spec_model regression (benchdiff rc=$rc)" >&2
    exit "$rc"
  fi

  # observatory sampler-overhead gate (docs/OBSERVABILITY.md "History &
  # watchdog"): re-run the obs_overhead rung and diff the on/off
  # throughput RATIO against the recorded baseline. The rung samples at
  # a 1000x compressed cadence, so the ratio is a hard upper bound on
  # production overhead; pure-python and platform-independent (the
  # artifact stamps "cpu" always, so the gate never cross-platform
  # refuses). Threshold 0.25 absorbs shared-CPU noise on a ~0.9 ratio —
  # a sampler regression big enough to matter at the production cadence
  # would crater the compressed-cadence ratio far past it.
  echo "[lint] obs_overhead rung vs BENCH_obs_overhead_r01.json"
  FRESH="$(mktemp "${TMPDIR:-/tmp}/obs_overhead.XXXXXX.json")"
  "$PY" bench.py obs_overhead | tail -1 > "$FRESH"
  rc=0
  "$PY" scripts/benchdiff.py BENCH_obs_overhead_r01.json "$FRESH" \
    --threshold 0.25 || rc=$?
  rm -f "$FRESH"
  if [ "$rc" -ne 0 ]; then
    echo "[lint] obs_overhead regression (benchdiff rc=$rc)" >&2
    exit "$rc"
  fi
fi

# interleaving-fuzzer smoke (docs/SIMULATION.md "The interleaving
# fuzzer"): the fleet-election scenario under 3 perturbed schedules must
# stay finding-free. Bounded (~4s, fully virtual time); the full 20-
# schedule sweeps over every clean scenario live in tests/test_simnet_fuzz.py.
# SKIP_FUZZ=1 skips it.
if [ "${SKIP_FUZZ:-0}" != "1" ]; then
  echo "[lint] interleaving fuzzer smoke (fleet_election, 3 schedules)"
  "$PY" -m bee2bee_tpu.simnet.fuzz --scenario fleet_election --schedules 3
fi

# telemetry smoke (docs/OBSERVABILITY.md): loopback node + one generation;
# /metrics must parse as Prometheus text with the mandatory series present.
# SKIP_SMOKE=1 skips it (e.g. environments without aiohttp sockets).
if [ "${SKIP_SMOKE:-0}" != "1" ]; then
  echo "[lint] telemetry smoke"
  "$PY" scripts/telemetry_smoke.py
fi

echo "[lint] ok"
