"""Serving-engine scale-out: sessions sustained per GPU as the pool grows.

Uses compute-free `StubSession`s (modeled GPU/network timing, no JAX math)
so the numbers are pure engine/scheduler behaviour. Two questions:

  1. capacity — for each pool size, the largest fleet whose mean mIoU stays
     at/above ``TARGET_MIOU`` (sessions sustained; the Appendix E scaling
     argument made measurable);
  2. placement — at the saturating fleet on 4 GPUs, does residency-aware
     `AffinityAware` assignment beat the affinity-blind `GainAware` ranking
     it shares a score with (migration time avoided -> phases + freshness)?

Emits ``BENCH_serving.json`` (sessions sustained, sessions-per-GPU, the
affinity comparison, the fused-training and dual-stream sections) next to
the repo root so future PRs can track the trajectory. ``--smoke`` is the CI
entry point: ``--smoke`` alone is the PR-1 single-GPU engine smoke;
``--smoke --gpus 4`` additionally asserts >=3x sustained-session scaling
from 1 -> 4 GPUs under the fair policy and that affinity beats blind
assignment; ``--smoke --fused`` asserts that coalesced stacked train
launches (fuse_train, priced by the sublinear `GPUCostModel.train_batch_s`)
sustain MORE sessions on one GPU than the sequential engine, and that the
real-math fused wall-clock for 8 seg sessions x one phase is <= 0.6x
sequential; ``--smoke --overlap`` asserts the dual-stream device model
(label/train stream overlap + preemptible labeling, `serving.StreamModel`)
sustains STRICTLY more sessions on one fused GPU than the serialized
single-clock baseline at the same mIoU target, and records preemption +
per-stream utilization telemetry; ``--smoke --trace out.json`` is the
flight-recorder gate — it asserts a traced fused dual-stream run emits
byte-identical, schema-valid Chrome trace JSON (grant/train/select/encode
spans, counter tracks, nesting + concurrency invariants) without
perturbing the schedule, then runs the modeled-vs-measured cost-model
drift audit on the real fused math (``observability`` section of
BENCH_serving.json); ``--smoke --chaos`` is the chaos gate — under the
seeded reference `FaultPlan` (lossy links, an uplink and a downlink
outage, one device crash, a thermal slowdown) the fleet must conserve
requests (enqueued == granted + dropped + queued), recover every crashed
grant through the gpu_done watchdog, retry lost uploads with backoff,
supersede stale deltas rather than blindly retransmit, and keep the mean
mIoU within a bounded gap of the fault-free fleet — while
``FaultPlan.none()`` stays bit-identical to running with no plan at all
(``chaos`` section of BENCH_serving.json).

``--smoke --sharded`` is the sharded-execution gate (run under >= 2 jax
devices — ``scripts/ci.sh --sharded`` forces 4 host-platform devices): D
co-resident fused groups dispatched on D real pool devices
(`core.batched.train_phases_sharded` over `GPUPool(device_backend="jax")`)
must reproduce the modeled single-device path — wire masks byte-identical,
fp16 wire deltas within 1 ULP, the serial all-None path byte-identical —
while the per-device modeled-vs-measured drift audit (``sharded_device``)
and the sharded-vs-serial wall-clock land in the ``sharded`` section of
BENCH_serving.json (the speedup assertion engages only on multi-core
hosts).

``--smoke --fleet`` is the fleet-control-plane gate — the struct-of-arrays
`FleetState` path (cohort events, vectorized policies/admission) must
reproduce the per-object engine bit-for-bit at small n across policies and
under chaos (`FaultPlan.none()` trace bytes included), sustain 10⁴ stub
sessions at >= 10x the per-object events/sec, and record the 10³ -> 10⁵
sweep (events/sec + peak RSS, O(1)-memory telemetry at 10⁵) in the
``fleet`` section of BENCH_serving.json.

Run: PYTHONPATH=src python -m benchmarks.serving_scale [--smoke]
     [--gpus 4] [--fused] [--overlap] [--trace out.json] [--chaos]
     [--fleet]
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import Timer, emit
from repro.core.scheduler import GPUCostModel
from repro.serving import (
    ClientNetwork,
    FleetState,
    LinkSpec,
    ServingConfig,
    ServingEngine,
    StreamModel,
    StubSession,
)

TARGET_MIOU = 0.84
BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serving.json")


def make_stub_fleet(n: int, *, stationary_frac: float = 0.3,
                    link: LinkSpec | None = None) -> list[StubSession]:
    """A mixed fleet: the head of the list is near-static (low sampling rate,
    slow decay), the rest dynamic — the same shape as the seg sweeps."""
    link = link or LinkSpec(up_kbps=500.0, down_kbps=2000.0)
    fleet = []
    for i in range(n):
        static = i < int(stationary_frac * n)
        fleet.append(StubSession(
            i,
            rate=0.15 if static else 1.0,
            dynamics=0.0005 if static else 0.004,
            net=ClientNetwork(link),
        ))
    return fleet


def make_fleet_state(n: int, *, stationary_frac: float = 0.3,
                     telemetry: str = "full") -> FleetState:
    """Struct-of-arrays twin of `make_stub_fleet`: same mixed fleet, same
    per-client parameters and link provisioning, array storage."""
    static = np.arange(n) < int(stationary_frac * n)
    return FleetState(
        n,
        rate=np.where(static, 0.15, 1.0),
        dynamics=np.where(static, 0.0005, 0.004),
        up_kbps=500.0, down_kbps=2000.0,
        telemetry=telemetry)


def run_fleet(n: int, *, n_gpus: int = 1, policy: str = "fair",
              duration: float = 240.0, max_queue: int = 32,
              fuse_train: int = 1, streams: StreamModel | None = None,
              cost: GPUCostModel | None = None,
              fuse_updates: bool = True, tracer=None,
              faults=None) -> dict:
    cfg_kw = {} if faults is None else {"faults": faults}
    engine = ServingEngine(
        make_stub_fleet(n), policy=policy, cost=cost or GPUCostModel(),
        cfg=ServingConfig(duration=duration, max_queue=max_queue,
                          n_gpus=n_gpus, fuse_train=fuse_train,
                          fuse_updates=fuse_updates,
                          streams=streams or StreamModel(), **cfg_kw),
        tracer=tracer)
    return engine.run()


def sessions_sustained(n_gpus: int, *, policy: str = "fair",
                       counts=(4, 8, 12, 16, 20, 24, 28, 32),
                       duration: float = 240.0,
                       target: float = TARGET_MIOU,
                       fuse_train: int = 1,
                       streams: StreamModel | None = None,
                       cost: GPUCostModel | None = None,
                       fuse_updates: bool = True) -> tuple[int, dict]:
    """Largest fleet in ``counts`` whose mean mIoU holds ``target`` on an
    ``n_gpus`` pool (0 if even the smallest fleet degrades past it)."""
    best, per_count = 0, {}
    for n in counts:
        r = run_fleet(n, n_gpus=n_gpus, policy=policy, duration=duration,
                      fuse_train=fuse_train, streams=streams, cost=cost,
                      fuse_updates=fuse_updates)
        per_count[n] = r
        if r["mean_miou"] >= target:
            best = max(best, n)
    return best, per_count


def _read_bench() -> dict:
    """Current BENCH_serving.json contents ({} if absent or unparsable)."""
    if os.path.exists(BENCH_PATH):
        try:
            with open(BENCH_PATH) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
    return {}


def _write_bench(update: dict) -> None:
    """Merge ``update`` into BENCH_serving.json (the pool sweep and the
    fused-training sweep each own different keys; neither clobbers the
    other's section)."""
    bench = _read_bench()
    bench.update(update)
    with open(BENCH_PATH, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(BENCH_PATH)}")


def run(counts=None, duration: float | None = None, policy: str = "gain",
        max_queue: int = 32, quick: bool = False) -> dict:
    """The PR-1 single-GPU engine sweep: events/sec + saturation telemetry."""
    if counts is None:
        counts = (4, 16) if quick else (4, 8, 16, 32, 64)
    if duration is None:
        duration = 60.0 if quick else 300.0
    out = {}
    for n in counts:
        fleet = make_stub_fleet(n)
        engine = ServingEngine(
            fleet, policy=policy, cost=GPUCostModel(),
            cfg=ServingConfig(duration=duration, max_queue=max_queue))
        with Timer() as t:
            r = engine.run()
        out[n] = r
        emit(f"serving_scale.{policy}.n{n}", t.us,
             f"evps={r['events_per_sec']:.0f};events={r['events_processed']};"
             f"gpu_util={r['gpu_utilization']:.2f};"
             f"deferral_rate={r['deferral_rate']:.2f};"
             f"drop={r['dropped_requests']};backlog={r['max_backlog']};"
             f"up_kbps={r['mean_up_kbps']:.1f};"
             f"down_kbps={r['mean_down_kbps']:.1f};"
             f"miou={r['mean_miou']:.3f}")
    return out


def run_pool_sweep(max_gpus: int = 4, *, counts=None, duration: float = 240.0,
                   affinity_n: int = 24, mode: str = "full") -> dict:
    """GPU-count sweep (sessions sustained vs pool size, fair policy) plus
    the affinity-on/off comparison at ``affinity_n`` clients on the full
    pool. Writes BENCH_serving.json."""
    if counts is None:
        counts = ((4, 8, 12, 24, 26) if mode == "smoke"
                  else (4, 8, 12, 16, 20, 24, 26, 28, 32))
    gpu_counts = sorted({1, max_gpus} | ({2} if max_gpus >= 4 else set()))
    if mode == "smoke":
        gpu_counts = [1, max_gpus]
    sustained = {}
    for ng in gpu_counts:
        with Timer() as t:
            best, per_count = sessions_sustained(ng, counts=counts,
                                                 duration=duration)
        sustained[ng] = best
        peak = per_count[max(c for c in counts if c <= max(best, counts[0]))]
        emit(f"serving_scale.pool.fair.g{ng}", t.us,
             f"sustained={best};per_gpu={best / ng:.1f};"
             f"target_miou={TARGET_MIOU};"
             f"util_at_peak={peak['gpu_utilization']:.2f};"
             f"migrations_at_peak={peak['migrations']}")

    affinity_cmp = {}
    for pol in ("gain", "affinity"):
        with Timer() as t:
            r = run_fleet(affinity_n, n_gpus=max_gpus, policy=pol,
                          duration=duration)
        affinity_cmp[pol] = {"mean_miou": r["mean_miou"],
                             "phases_served": r["phases_served"],
                             "migrations": r["migrations"],
                             "migration_s_total": r["migration_s_total"]}
        emit(f"serving_scale.affinity.{pol}.g{max_gpus}.n{affinity_n}", t.us,
             f"miou={r['mean_miou']:.4f};served={r['phases_served']};"
             f"migrations={r['migrations']};"
             f"migration_s={r['migration_s_total']:.1f}")

    bench = {
        "mode": mode,
        "target_miou": TARGET_MIOU,
        "duration_s": duration,
        "policy": "fair",
        "sessions_sustained": {str(g): sustained[g] for g in sustained},
        "sessions_per_gpu": {str(g): sustained[g] / g for g in sustained},
        "affinity_at_max_gpus": {"n_clients": affinity_n,
                                 "n_gpus": max_gpus, **affinity_cmp},
    }
    _write_bench(bench)
    return bench


def run_fused_sweep(fuse: int = 4, *, counts=(8, 10, 12, 14, 16, 20),
                    duration: float = 240.0) -> dict:
    """Fused cross-session training on ONE GPU: sessions sustained at the
    target mIoU with coalesced stacked launches (`fuse_train`) vs the
    sequential engine, under the batched-launch cost model
    (`GPUCostModel.train_batch_s`) — plus the real-math wall-clock compare
    from `kernels_bench`. Updates the ``fused_training`` section of
    BENCH_serving.json."""
    from benchmarks.kernels_bench import fused_phase_compare

    with Timer() as t:
        seq_best, _ = sessions_sustained(1, counts=counts, duration=duration)
        fused_best, per_count = sessions_sustained(
            1, counts=counts, duration=duration, fuse_train=fuse)
    peak = per_count[max(fused_best, counts[0])]
    emit(f"serving_scale.fused.g1.f{fuse}", t.us,
         f"sustained_seq={seq_best};sustained_fused={fused_best};"
         f"target_miou={TARGET_MIOU};"
         f"fused_launches_at_peak={peak['fused_launches']};"
         f"riders_at_peak={peak['rider_grants']}")
    wall = fused_phase_compare()
    bench = {
        "fused_training": {
            "fuse_train": fuse,
            "duration_s": duration,
            "target_miou": TARGET_MIOU,
            "sessions_sustained_1gpu": {"sequential": seq_best,
                                        "fused": fused_best},
            "fused_launches_at_peak": peak["fused_launches"],
            "rider_grants_at_peak": peak["rider_grants"],
            "wallclock_8_sessions_1_phase": wall,
        }
    }
    _write_bench(bench)
    return bench["fused_training"]


def run_update_sweep(fuse: int = 4, *, counts=(8, 10, 12, 14, 16, 18, 20),
                     duration: float = 240.0) -> dict:
    """Fused post-train update pipeline on ONE fused GPU: sessions sustained
    at the target mIoU when a fused grant's B selections + delta encodes are
    priced as one amortized `GPUCostModel.update_batch_s` launch
    (``fuse_updates``) vs B serial `update_solo_s` charges — under a cost
    model where the update path is actually priced (select_s +
    delta_comp_s_per_mb nonzero; the default model prices it at zero, where
    the two engines are bit-identical). Also records the real-math
    wall-clock compare from `kernels_bench.update_pipeline_compare` (8 seg
    sessions, stacked select + batched encode vs per-session, byte-identical
    wire). Updates the ``update_pipeline`` section of BENCH_serving.json."""
    from benchmarks.kernels_bench import update_pipeline_compare

    # 20 KB stub delta -> 0.1 s compress; selection launch 0.15 s: the
    # update stage is ~1/4 of a K=20 phase, the regime ShadowTutor/EdgeSync
    # report for partial-update production on edge-serving GPUs
    cost = GPUCostModel(select_s=0.15, delta_comp_s_per_mb=5.0)
    with Timer() as t:
        seq_best, _ = sessions_sustained(1, counts=counts, duration=duration,
                                         fuse_train=fuse, cost=cost,
                                         fuse_updates=False)
        bat_best, per_count = sessions_sustained(
            1, counts=counts, duration=duration, fuse_train=fuse, cost=cost,
            fuse_updates=True)
    peak = per_count[max(bat_best, counts[0])]
    up = peak["update_pipeline"]
    emit(f"serving_scale.update.g1.f{fuse}", t.us,
         f"sustained_per_session={seq_best};sustained_batched={bat_best};"
         f"target_miou={TARGET_MIOU};"
         f"batched_launches_at_peak={up['batched_launches']};"
         f"update_s_saved_at_peak={up['update_s_saved']:.1f}")
    wall = update_pipeline_compare()
    bench = {
        "update_pipeline": {
            "fuse_train": fuse,
            "duration_s": duration,
            "target_miou": TARGET_MIOU,
            "cost": {"select_s": cost.select_s,
                     "delta_comp_s_per_mb": cost.delta_comp_s_per_mb,
                     "update_setup_s": cost.update_setup_s,
                     "update_discount": cost.update_discount},
            "sessions_sustained_1gpu": {"per_session": seq_best,
                                        "batched": bat_best},
            "batched_launches_at_peak": up["batched_launches"],
            "batched_sessions_at_peak": up["batched_sessions"],
            "update_s_saved_at_peak": up["update_s_saved"],
            "wallclock_8_sessions_select_encode": wall,
        }
    }
    _write_bench(bench)
    return bench["update_pipeline"]


def run_overlap_sweep(fuse: int = 4, *, counts=(10, 12, 14, 16, 18, 20),
                      duration: float = 240.0, slowdown: float = 1.1,
                      preempt_cost: float = 0.02) -> dict:
    """Dual-stream device model on ONE fused GPU: sessions sustained at the
    target mIoU when teacher labeling overlaps training (label vs train
    streams, bounded ``slowdown`` while both are busy) with labeling
    launches preemptible at frame-batch boundaries — vs the serialized
    single-clock baseline (the PR-3 behavior) on the same fleet. Updates
    the ``dual_stream`` section of BENCH_serving.json with the capacity
    pair plus preemption and per-stream utilization telemetry at the
    overlapped peak."""
    streams = StreamModel(mode="overlap", slowdown=slowdown, preempt=True,
                          preempt_cost_s=preempt_cost)
    with Timer() as t:
        ser_best, _ = sessions_sustained(1, counts=counts, duration=duration,
                                         fuse_train=fuse)
        ovl_best, per_count = sessions_sustained(
            1, counts=counts, duration=duration, fuse_train=fuse,
            streams=streams)
    peak = per_count[max(ovl_best, counts[0])]
    su = peak["per_gpu_stream_utilization"]
    emit(f"serving_scale.overlap.g1.f{fuse}", t.us,
         f"sustained_serialized={ser_best};sustained_overlap={ovl_best};"
         f"target_miou={TARGET_MIOU};slowdown={slowdown};"
         f"preemptions_at_peak={peak['preemptions']};"
         f"label_util={su['label'][0]:.2f};train_util={su['train'][0]:.2f};"
         f"overlap_s={peak['overlap_s']:.0f}")
    bench = {
        "dual_stream": {
            "fuse_train": fuse,
            "duration_s": duration,
            "target_miou": TARGET_MIOU,
            "stream_model": {"mode": "overlap", "slowdown": slowdown,
                             "preempt": True,
                             "preempt_cost_s": preempt_cost},
            "sessions_sustained_1gpu": {"serialized": ser_best,
                                        "overlap": ovl_best},
            "preemptions_at_peak": peak["preemptions"],
            "preempted_frames_at_peak": peak["preempted_frames"],
            "overlap_s_at_peak": peak["overlap_s"],
            "stream_utilization_at_peak": {
                "label": su["label"][0], "train": su["train"][0]},
        }
    }
    _write_bench(bench)
    return bench["dual_stream"]


def run_trace_probe(trace_path: str, *, n: int = 8,
                    duration: float = 120.0) -> dict:
    """Flight-recorder gate: trace a fused dual-stream fleet twice and
    assert the Chrome trace JSON is byte-identical, schema-valid
    (`serving.validate_trace`: required counter tracks, non-negative
    durations, per-stream serial execution, concurrency bounds, grant
    nesting) and carries the full grant/train/select/encode span
    vocabulary; a serialized run must validate too, and tracing must not
    perturb the schedule (traced == untraced results). Writes the overlap
    trace to ``trace_path``."""
    from repro.serving import Tracer, validate_trace

    cost = GPUCostModel(select_s=0.15, delta_comp_s_per_mb=5.0)
    overlap = StreamModel(mode="overlap", slowdown=1.1, preempt=True,
                          preempt_cost_s=0.02)

    def traced(streams):
        tracer = Tracer()
        r = run_fleet(n, n_gpus=2, duration=duration, fuse_train=4,
                      streams=streams, cost=cost, tracer=tracer)
        return r, tracer.to_json()

    r1, j1 = traced(overlap)
    _, j2 = traced(overlap)
    assert j1 == j2, "trace not byte-identical across identical runs"
    trace = json.loads(j1)
    problems = validate_trace(trace)
    assert not problems, f"trace schema violations: {problems[:5]}"
    names = {e.get("name") for e in trace["traceEvents"]}
    for want in ("grant", "train", "select", "encode", "label_batch",
                 "delta", "frames"):
        assert want in names, f"trace missing {want!r} spans"
    _, js = traced(StreamModel())  # serialized: concurrency limit 1
    problems = validate_trace(json.loads(js))
    assert not problems, f"serialized trace violations: {problems[:5]}"
    # the recorder must be an observer: same schedule with tracing off
    r0 = run_fleet(n, n_gpus=2, duration=duration, fuse_train=4,
                   streams=overlap, cost=cost)
    drop = ("wall_s", "events_per_sec", "events_per_sec_steady",
            "observability")
    assert ({k: v for k, v in r0.items() if k not in drop}
            == {k: v for k, v in r1.items() if k not in drop}), (
        "tracing changed the simulated schedule")
    with open(trace_path, "w") as f:
        f.write(j1)
    print(f"wrote {trace_path} ({len(trace['traceEvents'])} events) — "
          f"open at https://ui.perfetto.dev")
    return trace


def run_chaos_probe(*, n: int = 12, n_gpus: int = 2,
                    duration: float = 240.0,
                    miou_gap_bound: float = 0.10) -> dict:
    """Chaos gate (`--chaos`): the engine under the reference `FaultPlan`
    (lossy links, an uplink and a downlink outage, one device crash, a
    thermal slowdown) must (1) keep `FaultPlan.none()` bit-identical to a
    fault-free run, (2) be deterministic under faults (same plan, same
    results), (3) balance its books — every request enqueued is granted,
    dropped, or still queued; every crashed grant is recovered; every lost
    delta resolves to retransmit/supersede/abandon — and (4) degrade
    gracefully: zero lost sessions and a bounded mean-mIoU gap vs the
    fault-free fleet. Also traces a chaos run (byte-identical, schema-valid,
    retry/outage/crash/supersede vocabulary). Writes the ``chaos`` section
    of BENCH_serving.json."""
    from repro.serving import FaultPlan, Tracer, validate_trace

    drop = ("wall_s", "events_per_sec", "events_per_sec_steady",
            "observability")

    def core(r):
        return {k: v for k, v in r.items() if k not in drop}

    kw = dict(n_gpus=n_gpus, duration=duration, fuse_train=4)
    with Timer() as t:
        # 1. faults-off golden: FaultPlan.none() == no plan, bit-for-bit
        base = run_fleet(n, **kw)
        none = run_fleet(n, faults=FaultPlan.none(), **kw)
        assert core(base) == core(none), (
            "FaultPlan.none() perturbed the fault-free engine")
        # 2. determinism under the reference plan
        plan = FaultPlan.reference(duration, n_gpus=n_gpus)
        r = run_fleet(n, faults=plan, **kw)
        r2 = run_fleet(n, faults=plan, **kw)
        assert core(r) == core(r2), (
            "chaos run not reproducible with the same seeded plan")
    ch = r["chaos"]
    # 3a. request conservation: nothing vanishes
    assert r["requests_enqueued"] == (r["requests_granted"]
                                      + r["dropped_requests"]
                                      + r["unserved_backlog"]), (
        f"request books don't balance: {r['requests_enqueued']} enqueued vs "
        f"{r['requests_granted']} granted + {r['dropped_requests']} dropped "
        f"+ {r['unserved_backlog']} queued")
    # 3b. every crashed grant recovered, every fault path exercised
    assert ch["device_crashes"] >= 1, "the crash window never fired"
    assert ch["grants_killed"] >= 1, (
        "the crash killed no grant (plan should hit a loaded device)")
    assert ch["grants_recovered"] == ch["grants_killed"], (
        f"{ch['grants_killed']} grants killed but only "
        f"{ch['grants_recovered']} recovered by the watchdog")
    assert ch["watchdog_fires"] == ch["grants_recovered"]
    assert ch["uploads_lost"] > 0 and ch["upload_retries"] > 0
    assert ch["deltas_lost"] > 0
    assert ch["deltas_superseded"] > 0, (
        "the downlink outage should supersede at least one stale delta")
    # 3c. every lost delta resolves (retransmitted, superseded or abandoned)
    assert (ch["deltas_retransmitted"] + ch["deltas_superseded"]
            + ch["deltas_abandoned"]) >= ch["deltas_lost"]
    assert ch["slowed_grants"] >= 1, "the slowdown window never fired"
    # 3d. zero lost sessions: every client still evaluates and the served
    # phase counts stay consistent
    assert len(r["miou_per_client"]) == n
    assert all(m == m for m in r["miou_per_client"]), "a session went dark"
    assert sum(r["phases_per_client"]) <= r["phases_served"]
    # 4. graceful degradation, not collapse
    gap = base["mean_miou"] - r["mean_miou"]
    assert 0.0 <= gap <= miou_gap_bound, (
        f"mIoU gap under faults is {gap:.3f} "
        f"(fault-free {base['mean_miou']:.3f} -> {r['mean_miou']:.3f}); "
        f"bound is {miou_gap_bound}")
    # 5. the flight recorder under chaos: deterministic, valid, and carries
    # the fault vocabulary without perturbing the schedule
    def traced():
        tracer = Tracer()
        rr = run_fleet(n, faults=plan, tracer=tracer, **kw)
        return rr, tracer.to_json()

    rt, j1 = traced()
    _, j2 = traced()
    assert j1 == j2, "chaos trace not byte-identical across identical runs"
    trace = json.loads(j1)
    problems = validate_trace(trace)
    assert not problems, f"chaos trace schema violations: {problems[:5]}"
    names = {e.get("name") for e in trace["traceEvents"]}
    for want in ("outage", "crash", "retry", "supersede"):
        assert want in names, f"chaos trace missing {want!r} events"
    assert core(rt) == core(r), "tracing perturbed the chaos schedule"
    emit(f"serving_scale.chaos.g{n_gpus}.n{n}", t.us,
         f"miou_gap={gap:.3f};crashes={ch['device_crashes']};"
         f"grants_recovered={ch['grants_recovered']};"
         f"upload_retries={ch['upload_retries']};"
         f"deltas_superseded={ch['deltas_superseded']};"
         f"shed={ch['requests_shed']}")
    bench = {
        "chaos": {
            "n_clients": n,
            "n_gpus": n_gpus,
            "duration_s": duration,
            "plan": {"seed": plan.seed, "up_loss": plan.up_loss,
                     "down_loss": plan.down_loss,
                     "outages": len(plan.outages),
                     "crashes": len(plan.crashes),
                     "slowdowns": len(plan.slowdowns)},
            "mean_miou_fault_free": base["mean_miou"],
            "mean_miou_under_faults": r["mean_miou"],
            "miou_gap": gap,
            "miou_gap_bound": miou_gap_bound,
            "final_staleness_max_s": r["chaos"]["final_staleness_max_s"],
            "link_outage_s": ch["link_outage_s"],
            "crash_s": ch["crash_s"],
            "grants_killed": ch["grants_killed"],
            "grants_recovered": ch["grants_recovered"],
            "sessions_recovered": ch["sessions_recovered"],
            "requests_shed": ch["requests_shed"],
            "upload_retries": ch["upload_retries"],
            "uploads_lost": ch["uploads_lost"],
            "upload_bytes_wasted": ch["upload_bytes_wasted"],
            "deltas_lost": ch["deltas_lost"],
            "deltas_retransmitted": ch["deltas_retransmitted"],
            "deltas_superseded": ch["deltas_superseded"],
            "retransmitted_bytes": ch["retransmitted_bytes"],
            "superseded_bytes": ch["superseded_bytes"],
            "dropped_frame_bytes": r["dropped_frame_bytes"],
        }
    }
    _write_bench(bench)
    return bench["chaos"]


def _rss_mb() -> float:
    """Current resident set in MB (VmRSS; falls back to the process peak)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _peak_rss_mb() -> float:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def run_fleet_probe(*, eq_n: int = 32, floor_n: int = 10_000,
                    floor_ratio: float = 10.0,
                    sweep=(1_000, 10_000, 100_000),
                    duration: float = 240.0,
                    eq_duration: float = 60.0) -> dict:
    """Fleet-control-plane gate (`--fleet`). Three parts:

    1. **equivalence** — at ``eq_n`` clients the `FleetState` engine must
       reproduce the per-object `StubSession` engine bit-for-bit (results
       minus wall-clock fields) across fair/edf/gain, pool sizes, an
       admission cap, and the seeded reference `FaultPlan` — and a traced
       `FaultPlan.none()` run must emit byte-identical trace JSON;
    2. **throughput floor** — at ``floor_n`` stubs (same fleet, same
       duration, full telemetry on both sides) the fleet path must sustain
       >= ``floor_ratio`` x the per-object events/sec, with identical
       results;
    3. **sweep** — 10³ -> 10⁵ clients, recording events/sec and resident
       memory per point (the 10⁵ point runs O(1)-memory ``moments``
       telemetry) into the ``fleet`` section of BENCH_serving.json.
    """
    from repro.serving import FaultPlan, Tracer

    drop = ("wall_s", "events_per_sec", "events_per_sec_steady",
            "observability")

    def core(r):
        return {k: v for k, v in r.items() if k not in drop}

    checks = []
    with Timer() as t:
        # 1. equivalence sweep: policies x pool sizes x admission cap
        for pol in ("fair", "edf", "gain"):
            for n_gpus in (1, 4):
                cfg = ServingConfig(duration=eq_duration, max_queue=32,
                                    n_gpus=n_gpus,
                                    admission_util_cap=(0.8 if n_gpus == 4
                                                        else None))
                r1 = ServingEngine(make_stub_fleet(eq_n), policy=pol,
                                   cfg=cfg).run()
                r2 = ServingEngine(make_fleet_state(eq_n), policy=pol,
                                   cfg=cfg).run()
                assert core(r1) == core(r2), (
                    f"fleet path diverged from per-object: policy={pol} "
                    f"n_gpus={n_gpus}")
                checks.append(f"{pol}/g{n_gpus}")
        # chaos: the reference plan must drive both paths identically
        plan = FaultPlan.reference(eq_duration, n_gpus=2)
        cfg = ServingConfig(duration=eq_duration, max_queue=32, n_gpus=2,
                            faults=plan)
        r1 = ServingEngine(make_stub_fleet(eq_n), policy="gain",
                           cfg=cfg).run()
        r2 = ServingEngine(make_fleet_state(eq_n), policy="gain",
                           cfg=cfg).run()
        assert core(r1) == core(r2), "fleet path diverged under chaos"
        checks.append("chaos")
        # FaultPlan.none() trace bytes: the recorder sees the same schedule
        tcfg = ServingConfig(duration=eq_duration, max_queue=32, n_gpus=2,
                             faults=FaultPlan.none())
        tr1, tr2 = Tracer(), Tracer()
        r1 = ServingEngine(make_stub_fleet(8), policy="gain", cfg=tcfg,
                           tracer=tr1).run()
        r2 = ServingEngine(make_fleet_state(8), policy="gain", cfg=tcfg,
                           tracer=tr2).run()
        assert core(r1) == core(r2), "traced fleet results diverged"
        assert tr1.to_json() == tr2.to_json(), (
            "fleet trace bytes differ from per-object under FaultPlan.none()")
        checks.append("trace-bytes")
    emit(f"serving_scale.fleet.eq.n{eq_n}", t.us,
         f"checks={len(checks)};duration={eq_duration}")

    # 2. throughput floor at floor_n, same duration both paths
    floor_cfg = ServingConfig(duration=duration, max_queue=32, n_gpus=4)
    with Timer() as t:
        r_fl = ServingEngine(make_fleet_state(floor_n), cfg=floor_cfg).run()
    fleet_evps = r_fl["events_per_sec"]
    with Timer() as t2:
        r_obj = ServingEngine(make_stub_fleet(floor_n), cfg=floor_cfg).run()
    obj_evps = r_obj["events_per_sec"]
    assert core(r_obj) == core(r_fl), (
        f"fleet path diverged from per-object at n={floor_n}")
    ratio = fleet_evps / max(obj_evps, 1e-9)
    assert ratio >= floor_ratio, (
        f"fleet events/sec is only {ratio:.1f}x the per-object path at "
        f"n={floor_n} ({fleet_evps:.0f} vs {obj_evps:.0f}); floor is "
        f"{floor_ratio}x")
    emit(f"serving_scale.fleet.floor.n{floor_n}", t.us,
         f"fleet_evps={fleet_evps:.0f};object_evps={obj_evps:.0f};"
         f"ratio={ratio:.1f};events={r_fl['events_processed']}")

    # 3. the 10^3 -> 10^5 sweep (largest point folds telemetry to moments)
    sweep_out = {}
    for n in sweep:
        telemetry = "moments" if n >= 100_000 else "full"
        with Timer() as t:
            r = ServingEngine(make_fleet_state(n, telemetry=telemetry),
                              cfg=floor_cfg).run()
        sweep_out[str(n)] = {
            "events_per_sec": r["events_per_sec"],
            "events_processed": r["events_processed"],
            "wall_s": r["wall_s"],
            "mean_miou": r["mean_miou"],
            "telemetry": telemetry,
            "rss_mb": round(_rss_mb(), 1),
        }
        emit(f"serving_scale.fleet.sweep.n{n}", t.us,
             f"evps={r['events_per_sec']:.0f};"
             f"events={r['events_processed']};telemetry={telemetry};"
             f"rss_mb={sweep_out[str(n)]['rss_mb']}")

    bench = {
        "fleet": {
            "duration_s": duration,
            "equivalence": {"n_clients": eq_n, "duration_s": eq_duration,
                            "checks": checks},
            "floor": {"n_clients": floor_n,
                      "events_per_sec_fleet": fleet_evps,
                      "events_per_sec_object": obj_evps,
                      "ratio": ratio, "floor_ratio": floor_ratio},
            "sweep": sweep_out,
            "peak_rss_mb": round(_peak_rss_mb(), 1),
        }
    }
    _write_bench(bench)
    return bench["fleet"]


def run_drift_probe(n_sessions: int = 4, k_iters: int = 4,
                    size: int = 16) -> dict:
    """Modeled-vs-measured cost audit on the REAL fused math: run a small
    seg fleet through `train_phases_fused` (force_stack) twice — first
    launch compiles, second is steady state — and fold the `core.timing`
    stage stats against `GPUCostModel`'s pricing (`serving.drift_report`).
    Updates the ``observability`` section of BENCH_serving.json."""
    from benchmarks.kernels_bench import _update_fleet
    from repro.core import timing
    from repro.core.batched import train_phases_fused
    from repro.serving import drift_report

    # the priced update pipeline from run_update_sweep, so select/encode
    # have a nonzero model to audit against
    cost = GPUCostModel(select_s=0.15, delta_comp_s_per_mb=5.0)
    timing.set_enabled(True)
    sessions = _update_fleet(n_sessions, k_iters, size)
    snap = timing.snapshot()
    with Timer() as t:
        train_phases_fused(sessions, 16.0, force_stack=True)  # first launch
        train_phases_fused(sessions, 26.0, force_stack=True)  # steady state
    stats = timing.delta(snap)
    drift = drift_report(cost, stats)
    measured = {k: e["measured_steady_s"] for k, e in drift.items()}
    assert sum(measured.values()) > 0.0, "no stage timings recorded"
    for stage in ("train_fused", "select_stacked", "encode_stacked"):
        assert stage in drift, f"stage {stage!r} not measured"
    bottleneck = max(measured, key=measured.get)
    emit(f"serving_scale.drift.b{n_sessions}.k{k_iters}", t.us,
         f"bottleneck={bottleneck};"
         f"measured_total_s={sum(measured.values()):.4f};"
         f"compile_s={timing.compile_s(stats):.2f}")
    bench = {
        "observability": {
            "n_sessions": n_sessions,
            "k_iters": k_iters,
            "cost": {"select_s": cost.select_s,
                     "delta_comp_s_per_mb": cost.delta_comp_s_per_mb,
                     "train_iter_s": cost.train_iter_s,
                     "train_batch_setup_s": cost.train_batch_setup_s,
                     "train_batch_discount": cost.train_batch_discount},
            "compile_s": timing.compile_s(stats),
            "drift": {stage: dict(e) for stage, e in drift.items()},
            "bottleneck": bottleneck,
        }
    }
    # the kernel gate (`kernels_bench --kernels`) owns observability.kernels;
    # top-level merge would clobber it, so carry it forward
    kernels = (_read_bench().get("observability") or {}).get("kernels")
    if kernels is not None:
        bench["observability"]["kernels"] = kernels
    _write_bench(bench)
    return bench["observability"]


def run_sharded_probe(n_groups: int = 4, group_b: int = 2, k_iters: int = 3,
                      size: int = 16) -> dict:
    """Real sharded execution on an actual device mesh: D co-resident fused
    groups run their full grant lifecycles (train -> select -> encode) on D
    concrete ``jax.Device``s at once (`core.batched.train_phases_sharded`
    over `GPUPool(device_backend="jax")` slot bindings; CPU-only hosts get
    the devices from `launch.host_mesh` / ``REPRO_HOST_DEVICES`` in
    scripts/env.sh).

    Four identical seg fleets each run one warm round (t=16, per-device
    executables compile) and one steady round (t=26):

      * modeled reference — per-group `train_phases_fused`, the engine's
        default path;
      * serial sharded — `train_phases_sharded` with all-None devices: the
        same refactored launch/commit code on the default device, asserted
        BYTE-identical to the reference (and the wall-clock baseline);
      * per-device dispatch — one async launch per group on its own
        device; identical jitted programs on same-kind devices, so wire
        masks must stay byte-identical and fp16 values within 1 ULP
        (byte-identity is recorded — and expected — but the asserted
        contract is the PR-7 tolerance);
      * SPMD one-launch — the groups concatenated along the session axis
        under a cached `NamedSharding` (same tolerance contract; GSPMD may
        re-fuse the math).

    The steady sharded rounds run under `core.timing`; `drift_report` must
    yield the per-device ``sharded_device`` modeled-vs-measured audit, and
    sessions-sustained comes from the measured steady round wall-clock vs
    the fleet's T_update. The sharded-beats-serial wall-clock assertion
    engages only on hosts with >= 2 CPU cores: forced host devices on a
    1-core container interleave on one core (~0.93x measured there — same
    reasoning as the interpret-mode kernel gates: correctness is the
    portable story, wall-clock needs real parallel hardware). Writes the
    ``sharded`` section of BENCH_serving.json."""
    import jax

    from benchmarks.kernels_bench import _f16_ulp_diff, _update_fleet
    from repro.core import batched, timing
    from repro.core.batched import train_phases_fused, train_phases_sharded
    from repro.launch.host_mesh import host_device_count_flag
    from repro.serving import drift_report
    from repro.serving.resources import GPUPool

    n_dev = len(jax.devices())
    assert n_dev >= 2, (
        f"sharded gate needs >= 2 jax devices, found {n_dev}. Force host "
        f"devices BEFORE jax initializes: `REPRO_HOST_DEVICES=4 source "
        f"scripts/env.sh` (XLA_FLAGS {host_device_count_flag(4)!r}), or "
        f"run `bash scripts/ci.sh --sharded`.")
    n_sessions = n_groups * group_b
    cost = GPUCostModel(select_s=0.15, delta_comp_s_per_mb=5.0)
    pool = GPUPool(n_gpus=n_groups, cost=cost, device_backend="jax")
    slot_devs = pool.jax_devices()
    assert pool.distinct_jax_devices == n_groups, (
        f"pool bound {pool.distinct_jax_devices} distinct devices; "
        f"expected {n_groups}")

    def fleet_groups():
        fleet = _update_fleet(n_sessions, k_iters, size)
        return [fleet[g * group_b:(g + 1) * group_b]
                for g in range(n_groups)]

    # four identical fleets (deterministic seeds), split into D groups of b
    g_ref, g_ser, g_dsp, g_spmd = (fleet_groups() for _ in range(4))
    batched.sharded_reset()

    # two warm rounds, then steady: round 0 pays the exec/kernel races and
    # per-device compiles; round 1 recompiles once more (the first round's
    # committed launch outputs change the input avals — opt-state scalars
    # come back strongly typed); round 2 is genuine steady state
    phases = (16.0, 26.0, 36.0)
    ref, ser, dsp, spm = [], [], [], []
    wall = {"serial": 0.0, "sharded": 0.0}
    snap = None
    for t_phase in phases:
        ref.append([train_phases_fused(g, t_phase, force_stack=True)
                    for g in g_ref])
        with Timer() as tm:
            ser.append(train_phases_sharded(
                g_ser, t_phase, devices=[None] * n_groups))
        wall["serial"] = tm.us / 1e6  # last (steady) round wins
        if t_phase == phases[-1]:  # clock + drift-audit the steady round
            timing.set_enabled(True)
            snap = timing.snapshot()
        with Timer() as tm:
            dsp.append(train_phases_sharded(g_dsp, t_phase,
                                            devices=slot_devs))
        wall["sharded"] = tm.us / 1e6
        spm.append(train_phases_sharded(g_spmd, t_phase, devices=slot_devs,
                                        spmd=True))
    stats = timing.delta(snap)

    def flat(rounds):
        return [d for r in rounds for grp in r for d in grp]

    d_ref, d_ser, d_dsp, d_spm = flat(ref), flat(ser), flat(dsp), flat(spm)
    assert len(d_ref) == len(phases) * n_sessions
    assert all(d is not None for d in d_ref)
    # serial sharded IS the refactored fused path on the default device
    assert all(a.packed_mask == b.packed_mask
               for a, b in zip(d_ref, d_ser)), (
        "all-None train_phases_sharded changed a streamed wire mask")
    assert all(np.array_equal(np.asarray(a.values), np.asarray(b.values))
               for a, b in zip(d_ref, d_ser)), (
        "all-None train_phases_sharded changed wire-delta bytes")
    equivalence = {"n_deltas": len(d_ref), "serial_byte_identical": True}
    for name, dd in (("dispatch", d_dsp), ("spmd", d_spm)):
        assert all(a.packed_mask == b.packed_mask
                   for a, b in zip(d_ref, dd)), (
            f"{name} sharded path changed a streamed wire mask")
        ulp = max(_f16_ulp_diff(a.values, b.values)
                  for a, b in zip(d_ref, dd))
        assert ulp <= 1, (
            f"{name} sharded wire-delta values drifted {ulp} f16 ULP (>1) "
            f"from the modeled path")
        equivalence[name] = {
            "values_max_f16_ulp": ulp,
            "values_byte_identical": int(sum(
                np.array_equal(np.asarray(a.values), np.asarray(b.values))
                for a, b in zip(d_ref, dd))),
        }

    info = batched.sharded_info()
    assert info["spmd_launches"] == len(phases), info
    # serial + dispatch paths, D launches each, every round
    assert info["dispatch_launches"] == 2 * len(phases) * n_groups, info
    assert info["distinct_devices"] == min(n_groups, n_dev), info

    drift = drift_report(cost, stats)
    sd = drift.get("sharded_device")
    assert sd is not None, "no per-device sharded timings recorded"
    per_dev = sd.get("per_device", {})
    assert sorted(per_dev) == list(range(n_groups)), (
        f"per-device drift covers slots {sorted(per_dev)}; "
        f"expected 0..{n_groups - 1}")
    for slot, e in per_dev.items():
        assert e["steady_calls"] >= 1 and e["measured_steady_s"] > 0.0, (
            f"device {slot} recorded no steady sharded time")
        assert e["modeled_steady_s"] > 0.0 and e["drift_ratio"] is not None
    ts = drift.get("train_sharded")
    assert ts is not None and ts["steady_calls"] >= 2, (
        "steady train_sharded batches (dispatch + spmd) not recorded")

    # sessions sustained from the MEASURED steady lifecycle (core.timing):
    # one sharded round serves n_sessions phases; the pool keeps up with
    # however many such cohorts fit in one T_update period
    round_s = ts["measured_per_call_s"]
    t_update = float(g_ref[0][0].cfg.t_update)
    assert 0.0 < round_s < t_update, (
        f"one sharded round took {round_s:.2f}s against a {t_update}s "
        f"update period — the pool cannot sustain even one cohort")
    sustained = int(n_sessions * t_update / round_s)

    ratio = wall["serial"] / max(wall["sharded"], 1e-9)
    multi_core = (os.cpu_count() or 1) >= 2
    if multi_core:
        assert ratio > 1.0, (
            f"sharded steady round ({wall['sharded']:.3f}s on "
            f"{info['distinct_devices']} devices) did not beat serial "
            f"dispatch ({wall['serial']:.3f}s) on a {os.cpu_count()}-core "
            f"host")
    emit(f"serving_scale.sharded.d{n_groups}.b{group_b}",
         wall["sharded"] * 1e6,
         f"devices={info['distinct_devices']};ratio={ratio:.2f};"
         f"speedup_asserted={multi_core};sustained={sustained};"
         f"dispatch_ulp={equivalence['dispatch']['values_max_f16_ulp']};"
         f"spmd_ulp={equivalence['spmd']['values_max_f16_ulp']}")
    bench = {
        "sharded": {
            "n_jax_devices": n_dev,
            "n_groups": n_groups,
            "group_b": group_b,
            "k_iters": k_iters,
            "cpu_count": os.cpu_count(),
            "equivalence": equivalence,
            "wallclock_steady_round": {
                "serial_s": wall["serial"], "sharded_s": wall["sharded"],
                "ratio_serial_over_sharded": ratio,
                "speedup_asserted": multi_core},
            "sessions_sustained": sustained,
            "round_s_measured": round_s,
            "t_update_s": t_update,
            "counters": info,
            "drift": {stage: dict(e) for stage, e in drift.items()
                      if stage in ("sharded_device", "train_sharded")},
        }
    }
    _write_bench(bench)
    return bench["sharded"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: 2 counts, short horizon")
    ap.add_argument("--policy", default="gain",
                    choices=("fair", "edf", "gain", "affinity"))
    ap.add_argument("--gpus", type=int, default=1,
                    help="pool size; >1 runs the GPU-count sweep")
    ap.add_argument("--fused", action="store_true",
                    help="fused cross-session training sweep: sessions "
                         "sustained on 1 GPU with coalesced stacked "
                         "launches + real-math wall-clock compare")
    ap.add_argument("--overlap", action="store_true",
                    help="dual-stream sweep: sessions sustained on 1 fused "
                         "GPU with label/train stream overlap + preemptible "
                         "labeling vs the serialized single-clock baseline")
    ap.add_argument("--update-pipeline", action="store_true",
                    help="fused update-pipeline sweep: sessions sustained "
                         "on 1 fused GPU with amortized batched "
                         "select+encode pricing vs per-session charges, "
                         "plus the real-math byte-identical wall-clock "
                         "compare")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos gate: deterministic fault injection "
                         "(lossy links, outages, a device crash, a "
                         "slowdown) must conserve requests, recover every "
                         "crashed grant via the watchdog, supersede stale "
                         "deltas, and hold a bounded mIoU gap vs the "
                         "fault-free fleet; FaultPlan.none() must be "
                         "bit-identical to no plan")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet control-plane gate: the struct-of-arrays "
                         "FleetState path must reproduce the per-object "
                         "engine bit-for-bit at small n (policies x pool "
                         "sizes x admission x chaos, byte-identical "
                         "traces) and sustain >= 10x its events/sec at "
                         "10^4 clients, then sweep 10^3 -> 10^5 recording "
                         "events/sec + resident memory")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded-execution gate (needs >= 2 jax devices; "
                         "ci.sh forces 4 host devices): co-resident fused "
                         "groups dispatched on real pool devices must "
                         "match the modeled path (masks byte-identical, "
                         "fp16 deltas within 1 ULP), with the per-device "
                         "modeled-vs-measured drift audit and the "
                         "sharded-vs-serial wall-clock (speedup asserted "
                         "on multi-core hosts only)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="flight-recorder gate: trace a fused dual-stream "
                         "fleet, assert byte-identical + schema-valid "
                         "Chrome trace JSON (written to PATH), and run the "
                         "modeled-vs-measured drift audit on the real "
                         "fused math")
    ap.add_argument("--duration", type=float, default=None)
    args = ap.parse_args()
    if args.smoke and args.sharded:
        sb = run_sharded_probe()
        wc = sb["wallclock_steady_round"]
        print(f"serving_scale sharded smoke OK "
              f"({sb['counters']['distinct_devices']} devices; "
              f"serial {wc['serial_s']:.3f}s vs sharded "
              f"{wc['sharded_s']:.3f}s, ratio "
              f"{wc['ratio_serial_over_sharded']:.2f}x"
              f"{'' if wc['speedup_asserted'] else ' (1-core host: speedup not asserted)'}; "
              f"dispatch ulp {sb['equivalence']['dispatch']['values_max_f16_ulp']}, "
              f"spmd ulp {sb['equivalence']['spmd']['values_max_f16_ulp']}; "
              f"sustained {sb['sessions_sustained']} sessions)")
        print("serving_scale smoke OK")
        return
    if args.smoke and args.fleet:
        fb = run_fleet_probe(duration=args.duration or 120.0)
        top = fb["sweep"][str(max(int(k) for k in fb["sweep"]))]
        print(f"serving_scale fleet smoke OK "
              f"({fb['floor']['ratio']:.0f}x per-object events/sec at "
              f"n={fb['floor']['n_clients']}; top of sweep "
              f"{top['events_per_sec']:.2e} ev/s, {top['rss_mb']:.0f} MB "
              f"RSS, telemetry={top['telemetry']})")
        print("serving_scale smoke OK")
        return
    if args.smoke and args.chaos:
        cb = run_chaos_probe()
        print(f"serving_scale chaos smoke OK "
              f"(mIoU {cb['mean_miou_fault_free']:.3f} -> "
              f"{cb['mean_miou_under_faults']:.3f}, gap "
              f"{cb['miou_gap']:.3f} <= {cb['miou_gap_bound']}; "
              f"{cb['grants_killed']} crashed grants all recovered, "
              f"{cb['upload_retries']} upload retries, "
              f"{cb['deltas_superseded']} deltas superseded)")
        print("serving_scale smoke OK")
        return
    if args.smoke and args.trace:
        trace = run_trace_probe(args.trace)
        ob = run_drift_probe()
        print(f"serving_scale trace smoke OK "
              f"({len(trace['traceEvents'])} trace events; drift bottleneck "
              f"{ob['bottleneck']}, "
              f"compile {ob['compile_s']:.1f}s)")
        print("serving_scale smoke OK")
        return
    if args.smoke and args.update_pipeline:
        ub = run_update_sweep()
        seq = ub["sessions_sustained_1gpu"]["per_session"]
        bat = ub["sessions_sustained_1gpu"]["batched"]
        assert seq > 0, "per-session update pricing sustains nothing"
        assert bat >= seq, (
            f"batched update pipeline should never sustain fewer sessions "
            f"(got {bat} vs per-session {seq})")
        assert ub["update_s_saved_at_peak"] > 0.0
        wall = ub["wallclock_8_sessions_select_encode"]
        assert wall["byte_identical"], "batched encode changed wire bytes"
        assert wall["ratio"] <= 0.6, (
            f"batched select+encode for 8 sessions is {wall['ratio']:.2f}x "
            f"sequential; expected <= 0.6x")
        print(f"serving_scale update-pipeline smoke OK (sustained {seq} -> "
              f"{bat} sessions on 1 GPU, select+encode {wall['ratio']:.2f}x, "
              f"{ub['update_s_saved_at_peak']:.1f}s device time saved at "
              f"peak)")
        print("serving_scale smoke OK")
        return
    if args.smoke and args.overlap:
        ob = run_overlap_sweep()
        ser = ob["sessions_sustained_1gpu"]["serialized"]
        ovl = ob["sessions_sustained_1gpu"]["overlap"]
        assert ser > 0, "serialized fused 1-GPU engine sustains nothing"
        assert ovl > ser, (
            f"dual-stream overlap should sustain strictly more sessions on "
            f"one GPU than the serialized clock (got {ovl} vs {ser})")
        su = ob["stream_utilization_at_peak"]
        assert su["label"] > 0.0 and su["train"] > 0.0
        assert ob["overlap_s_at_peak"] > 0.0
        print(f"serving_scale overlap smoke OK (sustained {ser} -> {ovl} "
              f"sessions on 1 GPU, {ob['preemptions_at_peak']} preemptions "
              f"at peak)")
        print("serving_scale smoke OK")
        return
    if args.smoke and args.fused:
        fb = run_fused_sweep()
        seq = fb["sessions_sustained_1gpu"]["sequential"]
        fus = fb["sessions_sustained_1gpu"]["fused"]
        assert seq > 0, "sequential 1-GPU engine sustains nothing"
        assert fus > seq, (
            f"fused training should sustain more sessions on one GPU "
            f"(got {fus} vs sequential {seq})")
        ratio = fb["wallclock_8_sessions_1_phase"]["ratio"]
        assert ratio <= 0.6, (
            f"fused wall-clock for 8 sessions x 1 phase is {ratio:.2f}x "
            f"sequential; expected <= 0.6x")
        print(f"serving_scale fused smoke OK (sustained {seq} -> {fus} "
              f"sessions on 1 GPU, wall-clock {ratio:.2f}x)")
        print("serving_scale smoke OK")
        return
    if args.smoke:
        if args.gpus <= 1:  # the pool smoke below is its own gate; don't
            # repeat the single-GPU sweep ci.sh already ran separately
            out = run(duration=args.duration, policy=args.policy, quick=True)
            assert all(r["events_processed"] > 0 for r in out.values())
            assert all(r["mean_up_kbps"] > 0 for r in out.values())
        else:
            bench = run_pool_sweep(args.gpus, mode="smoke")
            s1 = bench["sessions_sustained"]["1"]
            sg = bench["sessions_sustained"][str(args.gpus)]
            assert s1 > 0, "1-GPU pool sustains nothing at the target mIoU"
            assert sg >= 3 * s1, (
                f"sustained sessions scaled {sg}/{s1} = {sg / max(s1, 1):.1f}x "
                f"from 1 -> {args.gpus} GPUs; expected >= 3x")
            aff = bench["affinity_at_max_gpus"]
            assert (aff["affinity"]["mean_miou"] > aff["gain"]["mean_miou"]
                    or aff["affinity"]["phases_served"]
                    > aff["gain"]["phases_served"]), (
                "affinity-aware placement should beat blind assignment")
            print(f"serving_scale pool smoke OK "
                  f"(sustained {s1} -> {sg} sessions, affinity beats blind)")
        print("serving_scale smoke OK")
    else:
        run(duration=args.duration, policy=args.policy)
        if args.gpus > 1:
            run_pool_sweep(args.gpus, duration=args.duration or 240.0)
        if args.fused:
            run_fused_sweep(duration=args.duration or 240.0)
        if args.overlap:
            run_overlap_sweep(duration=args.duration or 240.0)
        if args.update_pipeline:
            run_update_sweep(duration=args.duration or 240.0)
        if args.chaos:
            run_chaos_probe(duration=args.duration or 240.0)
        if args.fleet:
            run_fleet_probe(duration=args.duration or 240.0)
        if args.sharded:
            run_sharded_probe()


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    main()
