#!/usr/bin/env bash
# CI entry point: tier-1 tests + a fast serving-runtime smoke.
# Run from the repo root:  bash scripts/ci.sh
set -uo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Environment hygiene lives in scripts/env.sh (sourceable on its own for
# accelerator hosts / one-off shells): TF log silencing, guarded tcmalloc
# preload, and — when REPRO_HOST_DEVICES is set — the XLA_FLAGS forced
# host-platform device count the sharded gate runs under.
. "$(dirname "$0")/env.sh"

# `bash scripts/ci.sh --kernels` runs ONLY the Pallas kernel gate (fast
# local loop for kernel work); the full run includes it as its last gate.
if [ "${1:-}" = "--kernels" ]; then
    echo "== kernel gate: benchmarks.kernels_bench --kernels =="
    python -m benchmarks.kernels_bench --kernels
    exit $?
fi

# `bash scripts/ci.sh --chaos` runs ONLY the chaos gate (fast local loop
# for fault-injection work); the full run includes it below.
if [ "${1:-}" = "--chaos" ]; then
    echo "== chaos gate: benchmarks.serving_scale --smoke --chaos =="
    python -m benchmarks.serving_scale --smoke --chaos
    exit $?
fi

# `bash scripts/ci.sh --fleet` runs ONLY the fleet control-plane gate (fast
# local loop for FleetState / cohort-event work); the full run includes it.
if [ "${1:-}" = "--fleet" ]; then
    echo "== fleet gate: benchmarks.serving_scale --smoke --fleet =="
    python -m benchmarks.serving_scale --smoke --fleet
    exit $?
fi

# `bash scripts/ci.sh --sharded` runs ONLY the sharded-execution gate in a
# child process with 4 forced host devices (the flag must be set before
# jax initializes, so it cannot ride inside an already-warm process); the
# full run includes it below.
if [ "${1:-}" = "--sharded" ]; then
    echo "== sharded gate: benchmarks.serving_scale --smoke --sharded (4 host devices) =="
    XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
        python -m benchmarks.serving_scale --smoke --sharded
    exit $?
fi

echo "== tier-1 gate: pytest =="
python -m pytest -q
tier1=$?

echo "== serving smoke: benchmarks.serving_scale --smoke =="
python -m benchmarks.serving_scale --smoke
smoke=$?

echo "== multi-GPU serving smoke: benchmarks.serving_scale --smoke --gpus 4 =="
# asserts >=3x sustained-session scaling 1 -> 4 GPUs (fair policy) and that
# affinity-aware placement beats blind assignment; refreshes BENCH_serving.json
python -m benchmarks.serving_scale --smoke --gpus 4
pool_smoke=$?

echo "== fused-training smoke: benchmarks.serving_scale --smoke --fused =="
# asserts coalesced stacked train launches sustain MORE sessions on 1 GPU
# than the sequential engine, and that the real-math fused wall-clock for
# 8 seg sessions x one phase is <= 0.6x sequential; updates the
# fused_training section of BENCH_serving.json
python -m benchmarks.serving_scale --smoke --fused
fused_smoke=$?

echo "== update-pipeline smoke: benchmarks.serving_scale --smoke --update-pipeline =="
# asserts the fused post-train update pipeline (stacked selection + batched
# delta encode, amortized update_batch_s pricing) sustains at least as many
# sessions on one fused GPU as per-session pricing, that the real-math
# batched select+encode for 8 seg sessions is <= 0.6x sequential wall-clock
# with byte-identical wire deltas; updates the update_pipeline section of
# BENCH_serving.json
python -m benchmarks.serving_scale --smoke --update-pipeline
update_smoke=$?

echo "== dual-stream smoke: benchmarks.serving_scale --smoke --overlap =="
# asserts the dual-stream device model (label/train stream overlap with
# preemptible labeling launches) sustains STRICTLY more sessions on one
# fused GPU than the serialized single-clock baseline at the same mIoU
# target; records preemption + per-stream utilization telemetry in the
# dual_stream section of BENCH_serving.json
python -m benchmarks.serving_scale --smoke --overlap
overlap_smoke=$?

echo "== flight-recorder smoke: benchmarks.serving_scale --smoke --trace =="
# asserts a traced fused dual-stream run emits byte-identical, schema-valid
# Chrome trace JSON (required counter tracks, non-negative durations,
# per-stream serial execution, cross-stream concurrency bounds, grant
# nesting) without perturbing the schedule, then runs the modeled-vs-
# measured cost-model drift audit on the real fused math; writes the trace
# artifact and the observability section of BENCH_serving.json
trace_out="$(mktemp -t serving_trace.XXXXXX.json)"
python -m benchmarks.serving_scale --smoke --trace "$trace_out"
trace_smoke=$?
rm -f "$trace_out"

echo "== chaos smoke: benchmarks.serving_scale --smoke --chaos =="
# asserts the engine under the seeded reference FaultPlan (lossy links,
# uplink + downlink outages, a device crash, a thermal slowdown) conserves
# requests (enqueued == granted + dropped + queued), recovers every crashed
# grant via the gpu_done watchdog, retries lost uploads with backoff,
# supersedes stale deltas instead of blindly retransmitting, and holds the
# mean-mIoU gap vs the fault-free fleet within bound — while
# FaultPlan.none() stays bit-identical to running with no plan; writes the
# chaos section of BENCH_serving.json
python -m benchmarks.serving_scale --smoke --chaos
chaos_smoke=$?

echo "== fleet smoke: benchmarks.serving_scale --smoke --fleet =="
# asserts the struct-of-arrays FleetState control plane reproduces the
# per-object engine bit-for-bit at small n (fair/edf/gain x pool sizes x
# admission cap x reference FaultPlan, byte-identical FaultPlan.none()
# traces) and sustains >= 10x the per-object events/sec at 10^4 clients,
# then sweeps 10^3 -> 10^5 clients (the top point on O(1)-memory moments
# telemetry) into the fleet section of BENCH_serving.json
python -m benchmarks.serving_scale --smoke --fleet
fleet_smoke=$?

echo "== sharded smoke: benchmarks.serving_scale --smoke --sharded (4 host devices) =="
# asserts, with 4 forced host-platform devices (scripts/env.sh), that the
# sharded fused path (train_phases_sharded over GPUPool device_backend=jax)
# reproduces the single-device modeled path — selection/wire masks
# byte-identical, fp16 wire deltas within 1 ULP, per-device dispatch
# byte-identical — and measures sharded-vs-serial wall-clock (the speedup
# assertion engages only on multi-core hosts; a 1-core container cannot
# physically run 4 devices in parallel); writes the sharded section of
# BENCH_serving.json with the per-device modeled-vs-measured drift
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
    python -m benchmarks.serving_scale --smoke --sharded
sharded_smoke=$?

echo "== kernel gate: benchmarks.kernels_bench --kernels =="
# asserts the Pallas serving kernels against their XLA references on the
# real fused path: byte-identical selection/wire masks, fp16 wire-delta
# values within 1 ULP, byte-identical top-k masks, a recorded auto-mode
# dispatch race, and finite roofline-fraction fields written to the
# observability.kernels section of BENCH_serving.json
python -m benchmarks.kernels_bench --kernels
kernel_gate=$?

echo "tier-1 gate exit=$tier1, serving smoke exit=$smoke, pool smoke exit=$pool_smoke, fused smoke exit=$fused_smoke, update smoke exit=$update_smoke, overlap smoke exit=$overlap_smoke, trace smoke exit=$trace_smoke, chaos smoke exit=$chaos_smoke, fleet smoke exit=$fleet_smoke, sharded smoke exit=$sharded_smoke, kernel gate exit=$kernel_gate"
[ "$tier1" -eq 0 ] && [ "$smoke" -eq 0 ] && [ "$pool_smoke" -eq 0 ] && [ "$fused_smoke" -eq 0 ] && [ "$update_smoke" -eq 0 ] && [ "$overlap_smoke" -eq 0 ] && [ "$trace_smoke" -eq 0 ] && [ "$chaos_smoke" -eq 0 ] && [ "$fleet_smoke" -eq 0 ] && [ "$sharded_smoke" -eq 0 ] && [ "$kernel_gate" -eq 0 ] && echo "CI OK"
exit $((tier1 | smoke | pool_smoke | fused_smoke | update_smoke | overlap_smoke | trace_smoke | chaos_smoke | fleet_smoke | sharded_smoke | kernel_gate))
